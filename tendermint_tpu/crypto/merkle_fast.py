"""Batched SHA-256 kernels for the merkle fast path (crypto/merkle.py).

App-hash merkle trees hash many short fixed-length messages per commit —
inner nodes are always 65 bytes (0x01 || left32 || right32), leaf items of
one kvstore level mostly share a length — so the whole tree level fits one
vectorized compression: pack n messages into an (n, padded_words) uint32
array and run the SHA-256 rounds as ~640 elementwise u32 ops over it.
SHA-256 is pure u32 arithmetic, so unlike the Ed25519 challenge hash
(ed25519_jax/sha512.py, u64 emulated as u32 pairs) no wide-word emulation
is needed; the same round function runs under numpy (host vectorized) or
``jax.numpy`` (device, jitted per padded-block count — the only static
shape). Differential tests pin both to hashlib; crypto/merkle.py routes
between hashlib / numpy / device and owns breaker + threshold policy.
"""

from __future__ import annotations

import logging
import struct
from typing import List

import numpy as np

logger = logging.getLogger("tmtpu.merkle")

_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_IV = (0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
       0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19)


def _sha256_words(xp, words, n_blocks: int):
    """SHA-256 over n padded messages; ``words`` is (n, n_blocks*16) u32
    big-endian schedule input. Returns 8 arrays of shape (n,). Generic
    over numpy / jax.numpy — u32 adds wrap identically on both."""
    u = xp.uint32

    def rotr(x, k: int):
        return (x >> u(k)) | (x << u(32 - k))

    n = words.shape[0]
    hs = [xp.full((n,), u(iv)) for iv in _IV]
    for blk in range(n_blocks):
        w = [words[:, 16 * blk + t] for t in range(16)]
        for t in range(16, 64):
            x15, x2 = w[t - 15], w[t - 2]
            s0 = rotr(x15, 7) ^ rotr(x15, 18) ^ (x15 >> u(3))
            s1 = rotr(x2, 17) ^ rotr(x2, 19) ^ (x2 >> u(10))
            w.append(w[t - 16] + s0 + w[t - 7] + s1)
        a, b, c, d, e, f, g, h = hs
        for t in range(64):
            s1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25)
            ch = (e & f) ^ (~e & g)
            t1 = h + s1 + ch + u(int(_K[t])) + w[t]
            s0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22)
            maj = (a & b) ^ (a & c) ^ (b & c)
            t2 = s0 + maj
            h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2
        hs = [hs[0] + a, hs[1] + b, hs[2] + c, hs[3] + d,
              hs[4] + e, hs[5] + f, hs[6] + g, hs[7] + h]
    return hs


def _pad_fixed(msgs: List[bytes], length: int) -> np.ndarray:
    """Pack n equal-length messages into their padded big-endian u32
    schedule words, shape (n, blocks*16)."""
    n = len(msgs)
    padded = ((length + 8) // 64 + 1) * 64
    buf = np.zeros((n, padded), dtype=np.uint8)
    if length:
        buf[:, :length] = np.frombuffer(
            b"".join(msgs), dtype=np.uint8).reshape(n, length)
    buf[:, length] = 0x80
    buf[:, padded - 8:] = np.frombuffer(
        struct.pack(">Q", length * 8), dtype=np.uint8)
    return buf.view(">u4").astype(np.uint32)


def _digests(hs_stacked: np.ndarray, n: int) -> List[bytes]:
    out = hs_stacked.astype(">u4").tobytes()
    return [out[i * 32:(i + 1) * 32] for i in range(n)]


def sha256_many_np(msgs: List[bytes]) -> List[bytes]:
    """Vectorized host path; all messages must share one length."""
    words = _pad_fixed(msgs, len(msgs[0]))
    hs = _sha256_words(np, words, words.shape[1] // 16)
    return _digests(np.stack(hs, axis=1), len(msgs))


# -- device path (jitted per padded-block count) ------------------------------

_jit_cache: dict = {}
_device_state: List[bool] = []  # lazily probed once


def device_ready() -> bool:
    if not _device_state:
        try:
            import jax

            _device_state.append(bool(jax.devices()))
        except (ImportError, RuntimeError) as e:
            # jax.devices() raises RuntimeError when no backend can be
            # initialized; said once (the probe is cached), never silent
            logger.warning("merkle device tier off: no jax backend (%s)", e)
            _device_state.append(False)
    return _device_state[0]


def _device_fn(n_blocks: int):
    fn = _jit_cache.get(n_blocks)
    if fn is None:
        import jax
        import jax.numpy as jnp

        def run(words):
            return jnp.stack(_sha256_words(jnp, words, n_blocks), axis=1)

        fn = jax.jit(run)
        _jit_cache[n_blocks] = fn
    return fn


def sha256_many_device(msgs: List[bytes]) -> List[bytes]:
    """Device path: same packing, jitted rounds, host fetch. Raises on any
    device trouble — the caller (crypto/merkle.py) owns breaker fallback."""
    words = _pad_fixed(msgs, len(msgs[0]))
    out = np.asarray(_device_fn(words.shape[1] // 16)(words))
    return _digests(out, len(msgs))
