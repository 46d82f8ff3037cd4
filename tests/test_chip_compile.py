"""Compiles of the verification plane for a DESCRIBED TPU v5e chip.

The TPU compiler is installed wherever the tests run, and it compiles for a
chip that is described and not attached: what it refuses here it refuses on
the chip, at no chip time. Nothing runs, so these tests say nothing about
verdicts or speed (tests/test_tpu_device.py and chip_smoke.py do, on the
chip).

Split by cost. Tier-1 compiles the building blocks of the main path at the
deployed lane width (about a second each). The whole kernels take one to
two minutes apiece and are marked ``slow``: run them before a chip call
(``pytest tests/test_chip_compile.py -m slow``), one process at a time.

Only one process may load the TPU's library, and it keeps it until exit.
So the topology is described inside a module-scoped fixture — never while
a module is imported — every compile happens in the test's own process,
and all of these tests live in this one file.
"""

import re
import time
from collections import Counter

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from tendermint_tpu.crypto.ed25519_jax import curve, field as F
from tendermint_tpu.crypto.ed25519_jax import scalar as S
from tendermint_tpu.crypto.ed25519_jax import sha512 as H
from tendermint_tpu.crypto.ed25519_jax import sharded
from tendermint_tpu.crypto.ed25519_jax import verify as V

B = 16            # sublanes of one deployed 2,048-signature chunk
BATCH = (B, V.LANE)
NBLK = 2          # vote sign-bytes (~110 B) pad to two SHA-512 blocks
MLEN = NBLK * 128 - 64   # message bytes of a two-block preimage template


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), axis_names=(sharded.AXIS,))


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip (the next one would warn and
    compile again): switch the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _fe(sh, batch=BATCH):
    """One field element in limb form."""
    return jax.ShapeDtypeStruct((F.NLIMBS,) + batch, jnp.uint32, sharding=sh)


def _u32(sh, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sh)


def _u8(sh, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=sh)


def _i32(sh, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sh)


# --- executed operations of a compiled program --------------------------------

_NOT_EXECUTED = {"parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast"}
_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")


def executed_ops(hlo_text):
    """Operations one call of a compiled program executes on the chip,
    loops weighted: the instructions of the entry computation, plus those
    of every ``while`` body times its trip count (every loop of the verify
    programs counts up from 0 to a constant: ``fori_loop(0, n)``, ``scan``;
    the bound is read off the loop's condition). Parameters, constants,
    tuples and bitcasts are not executed and not counted; a fusion counts
    once, whatever it fuses. Returns ``(total, Counter by opcode)``.

    A property of the build, not a time: it tells an in-place column build
    (an operation an update) from a fused one at no chip time, and PERF.md
    §5 keeps the reading beside each kernel time. What an operation costs
    on the chip is what it moves through memory (PERF.md §6, PR 33: a
    quarter of the operations read slower)."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        head = None if line.startswith(" ") else _COMPUTATION.match(line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = _INSTRUCTION.match(line)
            if m:
                cur.append((m.group(1), line))

    def trips(while_line):
        cond = re.search(r"condition=%?([\w.\-]+)", while_line).group(1)
        text = "\n".join(line for _, line in comps[cond])
        bounds = re.findall(r"s32\[\][^ ]* constant\((\d+)\)", text)
        assert len(bounds) == 1 and "direction=LT" in text, text
        return int(bounds[0])

    by = Counter()

    def walk(name, weight):
        for op, line in comps[name]:
            if op in _NOT_EXECUTED:
                continue
            by[op] += weight
            if op == "while":
                body = re.search(r"body=%?([\w.\-]+)", line).group(1)
                walk(body, weight * trips(line))

    walk(entry, 1)
    return sum(by.values()), by


# --- tier-1: building blocks at the deployed lane width ---------------------

@pytest.mark.parametrize("op", ["mul", "sqr", "inverse", "pow_p58"])
def test_field_op_compiles(one_chip, op):
    # the TPU-only miscompile of round 1 lived in field.mul
    fe = _fe(one_chip)
    if op == "mul":
        _compile(lambda a, b: F.carry(F.mul(a, b)), fe, fe)
    else:
        _compile(getattr(F, op), fe)


# What F.mul (and F.sqr, which is mul(a, a)) reads on the described v5e with
# the fused column build (PR 33), at 2 sublanes and at 16 alike: the
# operand's copy into fast memory, one fusion for its 17 limbs, one for all
# 34 columns, and carry's seven. The in-place build read 43 (sqr 63), 33
# (52) of them column updates.
FIELD_MUL_OPS = 11


@pytest.mark.parametrize("sublanes", [2, 16])
@pytest.mark.parametrize("op", ["mul", "sqr"])
def test_field_columns_are_one_fused_sum(one_chip, op, sublanes):
    """No column update comes back into mul / sqr: each in-place update is a
    device operation of its own, 34 a multiplication."""
    fe = _fe(one_chip, (sublanes, V.LANE))
    compiled = _compile(getattr(F, op), *([fe] * (2 if op == "mul" else 1)))
    text = compiled.as_text()
    total, by = executed_ops(text)
    assert "dynamic-update-slice" not in text
    assert total <= FIELD_MUL_OPS + 2, (total, dict(by))


def test_curve_add_compiles(one_chip):
    fe = _fe(one_chip)
    _compile(lambda *c: curve.add(curve.Point(*c[:4]), curve.Point(*c[4:])),
             *([fe] * 8))


def test_curve_decompress_compiles(one_chip):
    _compile(curve.decompress, _fe(one_chip), _u32(one_chip, *BATCH))


def test_curve_encode_compiles(one_chip):
    fe = _fe(one_chip)
    _compile(lambda *c: curve.encode(curve.Point(*c)), *([fe] * 4))


def test_sha512_blocks_compiles(one_chip):
    _compile(H.sha512_blocks, _u32(one_chip, NBLK, 32, *BATCH),
             _i32(one_chip, *BATCH))


def test_sc_reduce_digits_compiles(one_chip):
    _compile(S.sc_reduce_digits, _u32(one_chip, 16, *BATCH))


@pytest.mark.parametrize("n_cols", [4, 96])
def test_assemble_blocks_compiles(one_chip, n_cols):
    """The sparse wire format's on-device preimage build, u8 scatter
    included, at the narrowest and the widest diff-column bucket."""
    sh = one_chip
    _compile(V._assemble_blocks, _u8(sh, MLEN), _i32(sh, n_cols),
             _u8(sh, n_cols, *BATCH), _i32(sh, *BATCH),
             _u8(sh, 32, *BATCH), _u8(sh, 32, *BATCH))


def test_psum_tally_compiles_on_four_chip_mesh(mesh4):
    """The exact voting-power tally of sharded._sharded_step: masked limb
    planes summed per chip, one psum across the mesh."""
    from jax import shard_map

    def tally(verdict, power_limbs):
        masked = jnp.where(verdict[None], power_limbs, 0)
        local = jnp.sum(masked, axis=(1, 2))
        return jax.lax.psum(local, axis_name=sharded.AXIS)

    step = shard_map(tally, mesh=mesh4,
                     in_specs=(sharded.FLAG_SPEC, sharded.WORD_SPEC),
                     out_specs=P(), check_vma=False)
    b = 4 * B
    compiled = _compile(
        step,
        jax.ShapeDtypeStruct((b, V.LANE), jnp.bool_,
                             sharding=NamedSharding(mesh4, sharded.FLAG_SPEC)),
        jax.ShapeDtypeStruct((sharded.POWER_LIMBS, b, V.LANE), jnp.int32,
                             sharding=NamedSharding(mesh4, sharded.WORD_SPEC)))
    assert "all-reduce" in compiled.as_text()


# --- slow: the whole kernels at the shapes chip_smoke.py runs ---------------

# Executed operations of one verification pass over a batch (one call of
# ``_verify_kernel``, one chunk of a stream), loops weighted: 54,315-54,858
# with the fused column build (PR 33, lane buckets 128 to 2,048; 55,600 a
# chunk of the sparse stream), 198,130-201,203 with the in-place build,
# 175,000 of them column updates. The ceiling keeps that build from coming
# back unnoticed; a program of K chunks may execute K times as many.
VERIFY_OPS_CEILING = 60_000


def _report(name, compiled, t0, chunks=1):
    """Prints what the compile cost and the program's executed operations
    (PERF.md §5 keeps them beside each kernel time), and holds them under
    ``chunks`` times the ceiling."""
    m = compiled.memory_analysis()
    text = compiled.as_text()
    total, by = executed_ops(text)
    print(f"\nAOT {name}: compile {time.perf_counter() - t0:.1f}s "
          f"code {m.generated_code_size_in_bytes / 1e6:.1f}MB "
          f"args {m.argument_size_in_bytes / 1e6:.2f}MB "
          f"out {m.output_size_in_bytes / 1e6:.3f}MB "
          f"temp {m.temp_size_in_bytes / 1e6:.2f}MB "
          f"hlo {len(text) / 1e6:.1f}MB executed ops {total:,} "
          f"({by['fusion']:,} fusions)")
    assert total <= chunks * VERIFY_OPS_CEILING, (name, total, dict(by))


@pytest.mark.slow
@pytest.mark.parametrize("lanes", [128, 256, 2048])
def test_verify_kernel_compiles(one_chip, lanes):
    """The one-call buckets chip_smoke.py touches (verify._pad_to)."""
    sh, b = one_chip, lanes // V.LANE
    t0 = time.perf_counter()
    compiled = _compile(V._verify_kernel.__wrapped__,
                        _u32(sh, NBLK, 32, b, V.LANE), _i32(sh, b, V.LANE),
                        _u32(sh, 8, b, V.LANE))
    _report(f"_verify_kernel[{lanes}]", compiled, t0)


def _sparse_specs(sh, k, n_cols):
    return (_u8(sh, k, MLEN), _i32(sh, n_cols), _u8(sh, k, n_cols, *BATCH),
            _i32(sh, k, *BATCH), _u8(sh, k, 32, *BATCH),
            _u8(sh, k, 32, *BATCH), _u8(sh, k, 32, *BATCH))


@pytest.mark.slow
@pytest.mark.parametrize("k,n_cols", [(3, 4), (2, 4), (8, 96), (10, 4)])
def test_sparse_stream_kernel_compiles(one_chip, k, n_cols):
    """Segment shapes of chip_smoke.py: a 10,240-signature commit is five
    2,048-chunks split 3 + 2 with four diff columns (the timestamp bytes);
    a 16-block fast-sync window at 1,000 validators is sixteen chunks
    split 8 + 8 whose chunks straddle commits (96-column bucket); the
    one-device side of ``--chips 4`` runs 40,960 signatures as twenty
    chunks split 10 + 10."""
    t0 = time.perf_counter()
    compiled = _compile(V._verify_sparse_stream_kernel.__wrapped__,
                        *_sparse_specs(one_chip, k, n_cols))
    _report(f"_verify_sparse_stream_kernel[K={k},C={n_cols}]", compiled, t0,
            chunks=k)


@pytest.mark.slow
@pytest.mark.parametrize("k", [3])
def test_dense_stream_kernel_compiles(one_chip, k):
    sh = one_chip
    t0 = time.perf_counter()
    compiled = _compile(V._verify_stream_kernel.__wrapped__,
                        _u32(sh, k, NBLK, 32, *BATCH), _i32(sh, k, *BATCH),
                        _u32(sh, k, 8, *BATCH))
    _report(f"_verify_stream_kernel[K={k}]", compiled, t0, chunks=k)


@pytest.mark.slow
def test_sharded_step_compiles_on_four_chip_mesh(mesh4, monkeypatch):
    """The whole sharded step (verify kernel + psum tally) at 10,240
    signatures padded to 16,384 lanes over four chips."""
    b = 16384 // V.LANE
    ns = lambda spec: NamedSharding(mesh4, spec)  # noqa: E731
    # the step cache is keyed by device ids, and the described chips carry
    # the same ids as this process's CPU devices
    monkeypatch.setattr(sharded, "_STEP_CACHE", {})
    t0 = time.perf_counter()
    compiled = sharded._sharded_step(mesh4).lower(
        jax.ShapeDtypeStruct((NBLK, 32, b, V.LANE), jnp.uint32,
                             sharding=ns(sharded.BLOCK_SPEC)),
        jax.ShapeDtypeStruct((b, V.LANE), jnp.int32,
                             sharding=ns(sharded.FLAG_SPEC)),
        jax.ShapeDtypeStruct((8, b, V.LANE), jnp.uint32,
                             sharding=ns(sharded.WORD_SPEC)),
        jax.ShapeDtypeStruct((sharded.POWER_LIMBS, b, V.LANE), jnp.int32,
                             sharding=ns(sharded.WORD_SPEC))).compile()
    assert "all-reduce" in compiled.as_text()
    _report("sharded full_step[4 chips, 16384 lanes]", compiled, t0)


@pytest.mark.slow
def test_merkle_sha256_compiles(one_chip):
    """sha256_many_device's jitted body at the merkle inner-node width
    (65-byte messages, two blocks) and the device tier's minimum batch."""
    from tendermint_tpu.crypto import merkle_fast as mf

    t0 = time.perf_counter()
    compiled = _compile(
        lambda words: jnp.stack(mf._sha256_words(jnp, words, 2), axis=1),
        _u32(one_chip, 16384, 32))
    _report("merkle sha256[n=16384, 2 blocks]", compiled, t0)
