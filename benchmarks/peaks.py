"""Peaks of the chips the benchmark knows, and the work of one signature.

One table, keyed by ``device_kind`` as jax reports it. A device that is not
in the table is an error, never a default.

Source of the v5e row: Google Cloud documentation, "TPU v5e" system
architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
819 GB/s per chip.

The int8 figure is the MXU's. The verify kernels are plain ``jax.jit``
integer code that runs on the VPU, for which no peak is published; an
assumed one is not used. The roofline below is therefore an
*int8-equivalent* yardstick: it counts the work the ALGORITHM needs,
expressed in int8 multiply-adds, against the one published integer peak. A
later kernel (Pallas, MXU limb products) reads against the same yardstick.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e: 393 TOP/s int8, "
                  "819 GB/s HBM, 16 GB",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}: "
                       "add a row with its source to benchmarks/peaks.py "
                       "(a new file's table is not read)") from None


# --- the work of one Ed25519 verification (RFC 8032, cofactorless, as the
# --- host spec crypto/ed25519.py decides it) -------------------------------
#
# Counted from the algorithm, in multiplications in GF(2^255 - 19)
# (squarings count as multiplications):
#
#   decompress A      x = sqrt(u/v): one exponentiation to (p-5)/8, about
#                     252 squarings + 12 multiplications, plus ~6 to form
#                     u, v, v^3, v^7 and check               ~  270
#   [s]B - [h]A       a binary double-scalar ladder over 253 bits (Shamir's
#                     trick, B - A precomputed) in extended coordinates
#                     (Hisil et al. 2008): every bit costs a doubling,
#                     4S + 4M = 8; a column costs an addition unless both
#                     scalars have a zero bit there, 3/4 of 253 = 190
#                     additions at 9M (8M and the 2d*T product):
#                     253*8 + 190*9                          ~ 3,734
#   encode R'         one inversion (254 squarings + 11 multiplications)
#                     and two multiplications                ~  265
#
#   F = 270 + 253*8 + 190*9 + 265 = 4,269 field multiplications.
#
# One field multiplication, as a 256 x 256-bit schoolbook product of 32
# bytes by 32 bytes, is 32*32 = 1,024 byte products, each a multiply and an
# add: 2,048 int8 operations. (The reduction mod p is linear in the limbs
# and left out.) SHA-512 of R || A || M (two or three 128-byte blocks, 80
# rounds of 64-bit adds and rotates each) and the reduction of h mod L are
# under 1% of that and left out.
FIELD_MULS_PER_SIG = 270 + 253 * 8 + 190 * 9 + 265
INT8_OPS_PER_FIELD_MUL = 2 * 32 * 32
INT8_OPS_PER_SIG = FIELD_MULS_PER_SIG * INT8_OPS_PER_FIELD_MUL

# Bytes a signature needs across HBM at the least: its 64-byte signature,
# its 32-byte key (resident after the first commit of a validator set, so
# counted once here as an upper estimate), the bytes of its message that
# differ from the commit's template (a timestamp, ~8), and one verdict
# byte back.
BYTES_PER_SIG = 64 + 32 + 8 + 1


def verify_roofline_seconds(n_sigs: int, device_kind: str) -> dict:
    """The least time the chip could take for ``n_sigs`` verifications, by
    each bound, and which of the two is the larger (the roofline)."""
    p = peak(device_kind)
    ops_s = n_sigs * INT8_OPS_PER_SIG / p["int8_ops_per_s"]
    bytes_s = n_sigs * BYTES_PER_SIG / p["hbm_bytes_per_s"]
    return {"ops_s": ops_s, "bytes_s": bytes_s,
            "bound": "int8_ops" if ops_s >= bytes_s else "hbm_bytes",
            "seconds": max(ops_s, bytes_s)}
