"""Columnar sign-bytes: the zero-copy vote-pack fast path.

One commit's canonical sign-bytes share every byte except a handful of
timestamp positions. :class:`SignColumns` carries that structure, so the
batched device verifier does not have to join all rows into one (n, mlen)
matrix and diff-scan it per segment
(ed25519_jax/verify._sparse_from_rows) to find it again:

* ``template`` — one full row's bytes (every row is identical outside
  ``cols``);
* ``cols``     — the int32 byte positions that vary row to row;
* ``vals``     — an (n, C) uint8 matrix of each row's bytes at ``cols``.

``types/canonical.vote_sign_bytes_table`` encodes a whole commit as byte
matrices (numpy varints, no Python per row) and hands out both forms: rows
as ``bytes`` and, for rows of one length class, these columns. The
VerifyCommit* entries give a uniform commit to ``BatchVerifier.add_columns``
WHOLE, in place of rows: pubkeys, signatures and columns go down to
``prepare_sparse_stream``, which slices the sparse wire format out of these
arrays, and a row is built (``rows()``) only where something reads rows:
the host route, a re-verify after a device error, a precomputed-verdict
lookup, the one-call program. Row reconstruction is byte-identical to
``vote_sign_bytes`` (differentially tested), so accept/reject verdicts
cannot change.

numpy-only and jax-free: types/ code builds these without dragging the
device stack into encode paths.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


class SignColumns:
    """A batch of equal-length messages as template + varying columns.

    Behaves as a read-only sequence of ``bytes`` rows (len / indexing /
    iteration) so host fallback paths can consume it like a message list,
    while the device pack path reads the arrays directly.
    """

    __slots__ = ("template", "cols", "vals")

    def __init__(self, template: np.ndarray, cols: np.ndarray,
                 vals: np.ndarray):
        self.template = np.ascontiguousarray(template, dtype=np.uint8)
        self.cols = np.ascontiguousarray(cols, dtype=np.int32)
        self.vals = np.asarray(vals, dtype=np.uint8)
        if self.vals.ndim != 2 or self.vals.shape[1] != self.cols.shape[0]:
            raise ValueError(
                f"vals shape {self.vals.shape} does not match "
                f"{self.cols.shape[0]} columns")

    # -- sequence protocol (host fallback / prepare_batch compatibility) ----

    def __len__(self) -> int:
        return self.vals.shape[0]

    @property
    def mlen(self) -> int:
        return self.template.shape[0]

    def __getitem__(self, i) -> bytes:
        if isinstance(i, slice):
            raise TypeError("use .slice(a, b) for row ranges")
        row = self.template.copy()
        row[self.cols] = self.vals[i]
        return row.tobytes()

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- batch views ---------------------------------------------------------

    def slice(self, a: int, b: int) -> "SignColumns":
        """Rows [a, b) — a zero-copy view (template/cols shared, vals
        sliced) for per-segment sharding."""
        return SignColumns(self.template, self.cols, self.vals[a:b])

    def subset(self, idxs: Sequence[int]) -> "SignColumns":
        """Rows at ``idxs`` in order (fancy index copies only the (k, C)
        vals block — the commit-idx candidate selection VerifyCommit*
        performs)."""
        return SignColumns(self.template, self.cols,
                           self.vals[np.asarray(idxs, dtype=np.intp)])

    def rows(self) -> list:
        """Materialized bytes rows (host fallback; O(n*mlen)): one matrix,
        one ``tobytes``, one slice a row."""
        n, ml = len(self), self.mlen
        arr = np.empty((n, ml), dtype=np.uint8)
        arr[:] = self.template
        arr[:, self.cols] = self.vals
        buf = arr.tobytes()
        return [buf[o:o + ml] for o in range(0, n * ml, ml)]


def sign_columns_from_rows(rows: Sequence[bytes]) -> "Optional[SignColumns]":
    """Tx-side SignColumns analogue (mempool/ingest.py micro-batches).

    Votes get their columns from the encoder's own matrix
    (``vote_sign_bytes_table``); tx sign-bytes have no such encoder, but a
    micro-batch of same-shape envelopes still shares most
    bytes (magic, fee/nonce prefixes, payload padding). One vectorized
    diff-scan at PACK time — on the intake path, once per micro-batch —
    yields the same zero-copy structure, instead of the verifier
    re-discovering it per segment per dispatch.

    Returns None when there is no structure to exploit: fewer than 2
    rows, unequal lengths, or rows so dissimilar the columnar form would
    carry ≥ half the matrix anyway. Reconstruction is byte-identical to
    ``rows`` (differentially tested), so verdicts cannot change."""
    n = len(rows)
    if n < 2:
        return None
    mlen = len(rows[0])
    if mlen == 0 or any(len(r) != mlen for r in rows):
        return None
    arr = np.frombuffer(b"".join(rows), dtype=np.uint8).reshape(n, mlen)
    cols = np.flatnonzero((arr != arr[0]).any(axis=0)).astype(np.int32)
    if cols.shape[0] * 2 > mlen:
        return None
    return SignColumns(arr[0], cols, arr[:, cols])
