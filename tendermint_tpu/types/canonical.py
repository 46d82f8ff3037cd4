"""Canonical sign-bytes (reference types/canonical.go + proto canonical.pb.go).

These are the exact bytes validators sign and verifiers check — the payload of
the TPU batch-verify hot path. Encoding quirks that matter (verified against
canonical.pb.go:517-567):

* height/round are sfixed64 little-endian, omitted when zero;
* the Timestamp field is non-nullable: ALWAYS emitted, even for zero time;
* CanonicalBlockID is a nullable pointer: omitted for nil/zero block ids;
* inside CanonicalBlockID the part_set_header is non-nullable: always emitted;
* the whole message is varint length-prefixed (libs/protoio MarshalDelimited).
"""

from __future__ import annotations

import numpy as np

from ..libs import protowire as pw
from .basic import BlockID, SignedMsgType


def canonical_block_id_bytes(block_id: BlockID) -> "bytes | None":
    if block_id.is_zero():
        return None
    w = pw.Writer()
    w.bytes(1, block_id.hash)
    w.message(2, block_id.part_set_header.encode())
    return w.finish()


def vote_sign_bytes(
    chain_id: str,
    vote_type: SignedMsgType,
    height: int,
    round_: int,
    block_id: BlockID,
    timestamp_ns: int,
) -> bytes:
    """CanonicalVote, length-delimited (types/vote.go:93 VoteSignBytes)."""
    w = pw.Writer()
    w.varint(1, int(vote_type))
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    w.message_opt(4, canonical_block_id_bytes(block_id))
    w.message(5, pw.timestamp(timestamp_ns))
    w.string(6, chain_id)
    return pw.length_delimited(w.finish())


_NS = 1_000_000_000


def _varint_matrix(v):
    """Protobuf varints of a uint64 array: ``((n, W) uint8, (n,) lengths)``;
    row i's varint is ``out[i, :lengths[i]]`` (== ``pw.encode_varint``)."""
    top = int(v.max()) if v.size else 0
    width = max(1, -(-top.bit_length() // 7))
    word = np.uint64
    if top < 1 << 32:  # nanos always, seconds until 2106: half the bytes
        v, word = v.astype(np.uint32), np.uint32
    out = np.empty((v.shape[0], width), dtype=np.uint8)
    lens = np.ones(v.shape[0], dtype=np.intp)
    for k in range(width):
        rest = v >> word(7 * k) if k else v
        more = rest >= word(0x80)  # a further group follows this one
        out[:, k] = (rest & word(0x7F)) | (more.view(np.uint8) << 7)
        lens += more
    return out, lens


def _split_ns(timestamps_ns):
    """Unix nanoseconds -> (seconds, nanos) as uint64 arrays (seconds in
    two's complement, as protobuf encodes a negative int64), or None where
    a value's seconds do not fit int64."""
    n = len(timestamps_ns)
    try:
        ns = np.fromiter(timestamps_ns, dtype=np.int64, count=n)
        sec = ns // _NS  # floors, like divmod
        nanos = ns - sec * _NS
    except OverflowError:
        # past int64 NANOseconds: Go's zero time, which absent rows carry,
        # is -6.2e19 ns. Split in Python; the seconds fit
        pairs = [divmod(t, _NS) for t in timestamps_ns]
        try:
            sec = np.fromiter((p[0] for p in pairs), dtype=np.int64, count=n)
        except OverflowError:
            return None
        nanos = np.fromiter((p[1] for p in pairs), dtype=np.int64, count=n)
    return sec.view(np.uint64), nanos.view(np.uint64)


class VoteSignBytes:
    """One batch of canonical vote sign-bytes as byte matrices, one per
    length class (rows that vote for the same block id with timestamp
    varints of the same widths lie byte for byte under one another).
    ``rows()`` cuts the ``bytes`` out of the matrices; ``columns()`` hands
    a class over as crypto.signcols.SignColumns without building a row."""

    __slots__ = ("_n", "_groups", "_klass", "_pos", "_rows", "_columns")

    def __init__(self, n: int, groups, rows=None):
        # groups: [(row indices | None for every row, (m, mlen) uint8
        # matrix, lo, hi)]: bytes [lo, hi) hold the timestamp field, the
        # only ones that differ inside a class. None with ``rows`` given.
        self._n = n
        self._groups = groups
        self._rows = rows
        self._columns = None
        self._klass = self._pos = None
        if groups and len(groups) > 1:
            self._klass = np.empty(n, dtype=np.intp)
            self._pos = np.empty(n, dtype=np.intp)
            for k, (idx, _mat, _lo, _hi) in enumerate(groups):
                self._klass[idx] = k
                self._pos[idx] = np.arange(idx.shape[0])

    def __len__(self) -> int:
        return self._n

    def rows(self) -> "list[bytes]":
        """Every row as ``bytes``, in batch order (built once)."""
        if self._rows is None:
            out = [None] * self._n
            for idx, mat, _lo, _hi in self._groups:
                ml = mat.shape[1]
                buf = mat.tobytes()
                part = [buf[o:o + ml] for o in range(0, len(buf), ml)]
                if idx is None:
                    out = part
                else:
                    for i, row in zip(idx.tolist(), part):
                        out[i] = row
            self._rows = out
        return self._rows

    def columns(self, idxs=None):
        """The rows at ``idxs`` (every row when None) as SignColumns:
        template = the first of them, cols = the byte positions where any
        of them differs from it. None when they are not of one length
        class (nil votes mixed in, ragged timestamp varints)."""
        from ..crypto.signcols import SignColumns

        if not self._groups:
            return None
        if idxs is None:
            if len(self._groups) > 1:
                return None
            if self._columns is not None:
                return self._columns
        else:
            idxs = np.asarray(idxs, dtype=np.intp)
            if idxs.shape[0] == 0:
                return None
        if len(self._groups) == 1:
            _, mat, lo, hi = self._groups[0]
            whole = idxs is None or (
                idxs.shape[0] == self._n
                and np.array_equal(idxs, np.arange(self._n)))
            sub = mat if whole else mat[idxs]
        else:
            klass = self._klass[idxs]
            if (klass != klass[0]).any():
                return None
            _, mat, lo, hi = self._groups[int(klass[0])]
            sub = mat[self._pos[idxs]]
        diff = (sub[:, lo:hi] != sub[0, lo:hi]).any(axis=0)
        cols = (np.flatnonzero(diff) + lo).astype(np.int32)
        out = SignColumns(sub[0], cols, sub[:, cols])
        if idxs is None:
            self._columns = out
        return out


def vote_sign_bytes_table(
    chain_id: str,
    vote_type: SignedMsgType,
    height: int,
    round_: int,
    block_ids,
    which,
    timestamps_ns,
) -> VoteSignBytes:
    """THE batch builder of :func:`vote_sign_bytes`: row i votes for
    ``block_ids[which[i]]`` (``which`` None: every row for
    ``block_ids[0]``) at ``timestamps_ns[i]``.

    A commit's sign-bytes share every field but the timestamp and, for nil
    votes, the block id. The shared fields are encoded once; seconds and
    nanos become varint bytes for all rows at once (numpy), and each length
    class is one matrix: its first row's layout broadcast, the varint
    columns written in. No Python runs per row, and every row is
    byte-identical to :func:`vote_sign_bytes` (differentially tested)."""
    n = len(timestamps_ns)
    if n == 0:
        return VoteSignBytes(0, [])
    split = _split_ns(timestamps_ns)
    if split is None:  # seconds past int64: no varint matrix, row by row
        pick = which if which is not None else [0] * n
        return VoteSignBytes(n, None, rows=[
            vote_sign_bytes(chain_id, vote_type, height, round_,
                            block_ids[b], ts)
            for b, ts in zip(pick, timestamps_ns)])
    w = pw.Writer()
    w.varint(1, int(vote_type))
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    prefix = w.finish()
    sw = pw.Writer()
    sw.string(6, chain_id)
    suffix = sw.finish()
    ev = pw.encode_varint
    f4s = []
    for bid in block_ids:
        body = canonical_block_id_bytes(bid)
        # field 4, wire type 2 -> tag byte 0x22; omitted for zero ids
        f4s.append(b"" if body is None else b"\x22" + ev(len(body)) + body)

    sec, nanos = split
    sec_b, sec_l = _varint_matrix(sec)
    nan_b, nan_l = _varint_matrix(nanos)
    sec_l[sec == 0] = 0  # proto3: a zero scalar is left out
    nan_l[nanos == 0] = 0
    key = sec_l * 8 + nan_l  # sec_l <= 10, nan_l <= 5
    if which is not None:
        key = key + np.asarray(which, dtype=np.intp) * 128
    if int(key.min()) == int(key.max()):
        classes = [(int(key[0]), None)]
    else:
        keys, inverse = np.unique(key, return_inverse=True)
        classes = [(int(k), np.flatnonzero(inverse == c))
                   for c, k in enumerate(keys)]
    groups = []
    for k, idx in classes:
        f4, ls, ln = f4s[k >> 7], (k >> 3) & 15, k & 7
        sec_f = b"\x08" + bytes(ls) if ls else b""  # Timestamp field 1
        nan_f = b"\x10" + bytes(ln) if ln else b""  # Timestamp field 2
        ts_len = len(sec_f) + len(nan_f)
        body_len = len(prefix) + len(f4) + 2 + ts_len + len(suffix)
        # field 5, wire type 2 -> tag byte 0x2a; ts_len <= 17: one byte
        head = ev(body_len) + prefix + f4 + b"\x2a" + bytes([ts_len])
        row = np.frombuffer(head + sec_f + nan_f + suffix, dtype=np.uint8)
        take = slice(None) if idx is None else idx
        mat = np.empty((n if idx is None else idx.shape[0], row.shape[0]),
                       dtype=np.uint8)
        mat[:] = row
        lo = len(head)
        if ls:
            mat[:, lo + 1:lo + 1 + ls] = sec_b[take, :ls]
        if ln:
            at = lo + len(sec_f) + 1
            mat[:, at:at + ln] = nan_b[take, :ln]
        groups.append((idx, mat, lo, lo + ts_len))
    return VoteSignBytes(n, groups)


def _table_of(chain_id, vote_type, height, round_, block_ids, timestamps_ns):
    """vote_sign_bytes_table from one block id per row."""
    n = len(timestamps_ns)
    if n == 0:
        return VoteSignBytes(0, [])
    first = block_ids[0]
    if all(b is first for b in block_ids):
        return vote_sign_bytes_table(chain_id, vote_type, height, round_,
                                     [first], None, timestamps_ns)
    index: dict = {}
    which = np.fromiter((index.setdefault(b, len(index)) for b in block_ids),
                        dtype=np.intp, count=n)
    return vote_sign_bytes_table(chain_id, vote_type, height, round_,
                                 list(index), which, timestamps_ns)


def vote_sign_bytes_batch(
    chain_id: str,
    vote_type: SignedMsgType,
    height: int,
    round_: int,
    block_ids,
    timestamps_ns,
) -> "list[bytes]":
    """Batched :func:`vote_sign_bytes` over one commit's rows, as ``bytes``
    (:func:`vote_sign_bytes_table`'s rows)."""
    return _table_of(chain_id, vote_type, height, round_, block_ids,
                     timestamps_ns).rows()


def vote_sign_bytes_columns_batch(
    chain_id: str,
    vote_type: SignedMsgType,
    height: int,
    round_: int,
    block_ids,
    timestamps_ns,
):
    """Columnar form of :func:`vote_sign_bytes_batch`: a SignColumns
    (template + varying byte positions + per-row values) taken from the
    same matrix, or ``None`` when the rows are not structurally uniform
    (mixed block ids — nil votes — or timestamp encodings of different byte
    lengths, where rows shift relative to each other and a shared template
    does not exist). The device pack path (prepare_sparse_stream) consumes
    the arrays directly; no row is built."""
    return _table_of(chain_id, vote_type, height, round_, block_ids,
                     timestamps_ns).columns()


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id: BlockID,
    timestamp_ns: int,
) -> bytes:
    """CanonicalProposal, length-delimited (types/proposal.go ProposalSignBytes)."""
    w = pw.Writer()
    w.varint(1, int(SignedMsgType.PROPOSAL))
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    w.varint(4, pol_round)  # int64 varint (canonical.proto:25)
    w.message_opt(5, canonical_block_id_bytes(block_id))
    w.message(6, pw.timestamp(timestamp_ns))
    w.string(7, chain_id)
    return pw.length_delimited(w.finish())
