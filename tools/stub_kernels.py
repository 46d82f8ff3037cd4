"""Shape-identical stand-ins for the three ed25519 verify kernels: a test
fake. A per-device-ordinal executable of a real kernel takes minutes to
build on XLA:CPU; the multi-device tests and the chaos lane cell check
packing, sharding, ordering and re-sharding, which need only a verdict that
is a deterministic function of each item's packed bytes.
"""

from __future__ import annotations


def install_stub_kernels(V):
    """Swap ``V``'s verify kernels (``ed25519_jax/verify.py``) for stubs
    with the same arguments in and the same verdict shape out, and return
    a restore() callable. Host packing, transfer and dispatch stay real.

    The verdict is PER ITEM (no term over a whole template or column set):
    it must not depend on how a batch is cut into segments, so a sharded
    layout can be held to the single-device one."""
    import jax
    import jax.numpy as jnp

    orig = (V._verify_kernel, V._verify_stream_kernel,
            V._verify_sparse_stream_kernel)

    @jax.jit
    def stub_kernel(blocks, nblk, s_words):
        return (jnp.sum(blocks, axis=(0, 1), dtype=jnp.uint32)
                + jnp.sum(s_words, axis=0, dtype=jnp.uint32)
                + nblk.astype(jnp.uint32)) % 2 == 0

    @jax.jit
    def stub_stream(blocks, nblk, s_words):
        return (jnp.sum(blocks, axis=(1, 2), dtype=jnp.uint32)
                + jnp.sum(s_words, axis=1, dtype=jnp.uint32)
                + nblk.astype(jnp.uint32)) % 2 == 0

    @jax.jit
    def stub_sparse(templates, diff_cols, diff_vals, mlen, r_b, a_b, s_b):
        return (jnp.sum(diff_vals, axis=1, dtype=jnp.uint32)
                + jnp.sum(r_b, axis=1, dtype=jnp.uint32)
                + jnp.sum(a_b, axis=1, dtype=jnp.uint32)
                + jnp.sum(s_b, axis=1, dtype=jnp.uint32)
                + mlen.astype(jnp.uint32)) % 2 == 0

    V._verify_kernel = stub_kernel
    V._verify_stream_kernel = stub_stream
    V._verify_sparse_stream_kernel = stub_sparse

    def restore():
        (V._verify_kernel, V._verify_stream_kernel,
         V._verify_sparse_stream_kernel) = orig

    return restore
