"""Test env: force JAX onto CPU with 8 virtual devices so sharding/multi-chip
paths are exercised without TPU hardware. Speed is measured on the chip
(``python chip_smoke.py``, the benchmark), never here.

THE RULE: a tier-1 run builds each kind of device program on the CPU at one
shape, in one test file; every other test reaches the device seam through
the host backend (``TMTPU_BATCH_BACKEND=host``) or the ``device_standin``
fixture below. One XLA:CPU build of a whole verify program costs ~15 s of
tracing and 3-6 minutes of compile (the graph is unrolled; compiler flags do
not help), each xdist worker is its own process, and the driver's run starts
on an empty compile cache — a second shape, or a second file asking for the
same one, is minutes of the run's clock. ``BUILD_FILES`` below is the whole
list; the full shapes run on the chip (``chip_smoke.py``,
``tests/test_tpu_device.py`` under ``TM_ON_DEVICE=1``) and as described-chip
AOT compiles under ``-m slow`` (``tests/test_chip_compile.py``).

The pin works because nothing imports jax before this file does: pytest
loads conftest.py first, ``JAX_PLATFORMS=cpu`` in the environment or
``jax.config.update("jax_platforms", ...)`` before first use both hold,
and ``XLA_FLAGS`` is read when the CPU backend initializes (the first
``jax.devices()`` call, below). The two hard assertions make any
regression loud instead of silently running the suite on an accelerator
or on one device.

Set TM_ON_DEVICE=1 to skip the pin and run the on-device differential suite
(tests/test_tpu_device.py) against the real chip.
"""

import faulthandler
import os
import signal
import time

import numpy as np
import pytest

ON_DEVICE = os.environ.get("TM_ON_DEVICE") == "1"

# The files that build a real device program on the CPU, the program and its
# one shape (see THE RULE above). Collected first so that each lands on a
# worker of its own when the run starts and none queues behind another.
BUILD_FILES = (
    # jit_full_step (sharded.py) on a 2- and an 8-device mesh, 128 lanes a
    # device, NBLK 1: the one file with two builds (side by side, in two
    # threads), so it goes first
    "test_sharded_verify.py",
    # jit__verify_sparse_stream_kernel: K=2 chunks of 128 lanes, MLEN 192,
    # 32 diff columns
    "test_sparse_verify.py",
    # jit__verify_stream_kernel (the dense fallback): K=2 x 128 lanes, NBLK 2
    "test_segmented_stream.py",
    # jit__verify_kernel: 128 lanes, NBLK 1
    "test_ed25519_jax.py",
)

# What one test may cost: a cold build of a whole verify program is the
# slowest honest test (measured 146-180 s alone, 250-375 s with three to
# five others beside it on 8 cores, PR 26): the limit is about twice that,
# and the driver cuts the whole run at 1,470 s.
# A test that hits this waits on something — an unjoined thread, a socket
# without a deadline, a poll without an end: repair the wait, not the limit.
TEST_TIME_LIMIT_S = 600.0


@pytest.fixture(autouse=True)
def _test_time_limit(request):
    """Fail the test BY NAME, with the traceback of where it waited, and let
    the run go on (every xdist worker runs its tests on its main thread,
    where SIGALRM lands). A wait inside native code never hands the
    interpreter the signal (a jitted SHA-256 that XLA:CPU does not end held
    a worker for a whole run, PR 26): a minute later faulthandler writes
    every thread's stack and ends the process, which xdist reports as this
    test's crash and replaces with a new worker."""
    limit = TEST_TIME_LIMIT_S

    def on_alarm(signum, frame):
        pytest.fail(f"{request.node.nodeid} waited past the per-test limit "
                    f"of {limit:g} s (tests/conftest.py TEST_TIME_LIMIT_S)")

    prev = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + 60, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, prev)


class DeviceStandIn:
    """Answers where crypto/ed25519_jax/verify.py hands packed inputs to a
    compiled program, with no program: ``kernel`` stands in for
    ``_verify_kernel`` (the one-call path, batch_verify) and
    ``dispatch_stream`` for ``_dispatch_stream`` (stream segments, the
    multi-device lanes). Everything above the seam — routing, packing,
    segmenting, phase stamps, fetch, tally, breakers — runs for real.

    Verdicts come from ``rule(pk, msg, sig)``, by default the host spec
    (crypto/ed25519.verify): a planted bad signature is still refused. A
    test whose rows are not signatures sets its own rule; ``pack_s`` /
    ``dispatch_s`` sleep on either side of the pack stamp for tests that
    read the phase split; ``calls`` lists the rows of each dispatch."""

    def __init__(self):
        from tendermint_tpu.crypto import ed25519

        self.rule = ed25519.verify
        self.pack_s = self.dispatch_s = 0.0
        self.calls = []

    def kernel(self, blocks, nblk, s_words):
        # undo pack_device_inputs: row i is R || A || M || sha padding as
        # big-endian words down axes (0, 1), its s as little-endian words
        nblk = np.asarray(nblk)
        lanes = nblk.size
        rows = np.asarray(blocks).reshape(-1, lanes).T.astype(">u4").tobytes()
        ss = np.asarray(s_words).reshape(8, lanes).T.astype("<u4").tobytes()
        width = len(rows) // lanes
        out = np.zeros(lanes, dtype=bool)
        for i, nb in enumerate(nblk.reshape(-1)):
            if nb:  # zero blocks: a padding lane
                row = rows[i * width:(i + 1) * width]
                end = int(nb) * 128
                mlen = int.from_bytes(row[end - 8:end], "big") // 8 - 64
                out[i] = self.rule(row[32:64], row[64:64 + mlen],
                                   row[:32] + ss[i * 32:(i + 1) * 32])
        self.calls.append(int(np.count_nonzero(nblk)))
        return out.reshape(nblk.shape)

    def dispatch_stream(self, pks, msgs, sigs, chunk, device=None,
                        columns=None):
        from tendermint_tpu.crypto import phases
        from tendermint_tpu.crypto.ed25519_jax import verify as V

        self.calls.append(len(pks))
        msgs = V._rows_of(msgs, columns)  # a columnar segment has no rows
        ok = V._sig_pk_arrays(pks, sigs)[3]
        time.sleep(self.pack_s)
        phases.mark_pack_done()  # the stamp _dispatch_stream places
        time.sleep(self.dispatch_s)
        verdicts = np.zeros(-(-len(pks) // chunk) * chunk, dtype=bool)
        verdicts[:len(pks)] = [self.rule(*row)
                               for row in zip(pks, msgs, sigs)]
        return verdicts, ok


@pytest.fixture
def device_standin(monkeypatch):
    """The ONE dispatch stand-in (THE RULE at the top of this file), for a
    test that sets its rule or reads its calls."""
    from tendermint_tpu.crypto.batch import DEFAULT_DEVICE_THRESHOLD
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    standin = DeviceStandIn()
    monkeypatch.setattr(V, "_verify_kernel", standin.kernel)
    monkeypatch.setattr(V, "_dispatch_stream", standin.dispatch_stream)
    # A test that takes the stand-in reads the device route, so "auto"
    # routing must not depend on what ran before it in this worker: the
    # break-even is a start-up probe of this host's dispatch overhead,
    # cached for the process (beside five busy workers it moves), and the
    # tools' in-process self-tests (churn, crashmatrix, chaos_matrix) leave
    # TMTPU_BATCH_BACKEND=host behind in os.environ.
    monkeypatch.setenv("TMTPU_DEVICE_THRESHOLD",
                       str(DEFAULT_DEVICE_THRESHOLD))
    monkeypatch.delenv("TMTPU_BATCH_BACKEND", raising=False)
    return standin


@pytest.fixture(autouse=True)
def _no_build_by_accident(request, monkeypatch):
    """Outside BUILD_FILES the one-call kernel is ALWAYS stood in, asked for
    or not. A burst of 16 votes in a net test is enough to send a flush to
    the device (crypto/vote_batcher.py), on an executor thread that
    asyncio.run() then waits out: a cold build nobody asked for, in
    whichever test the timing picks. The stream seam stays real here: the
    tools' stub kernels (tools/stub_kernels.py) sit under it."""
    if (os.path.basename(str(request.node.fspath)) in BUILD_FILES
            + ("test_chip_compile.py", "test_tpu_device.py")
            or "device_standin" in request.fixturenames):
        return
    from tendermint_tpu.crypto.ed25519_jax import verify as V

    monkeypatch.setattr(V, "_verify_kernel", DeviceStandIn().kernel)


@pytest.fixture
def device_metrics():
    """A DeviceMetrics registry wired into crypto/phases.py for one test
    (``_reset_fault_state`` below unwires it)."""
    from tendermint_tpu.crypto import phases
    from tendermint_tpu.libs.metrics import DeviceMetrics, Registry

    m = DeviceMetrics(Registry("t"))
    phases.set_device_metrics(m)
    return m


@pytest.fixture(autouse=True)
def _reset_fault_state():
    """Fail-point counters, armed fault sites, and breaker state are
    process-global by design (subprocess nodes arm them from env) — reset
    around every test so one test's chaos can't leak into the next."""
    import sys

    from tendermint_tpu.crypto import phases
    from tendermint_tpu.crypto.breaker import (
        device_breaker,
        reset_lane_breakers,
    )
    from tendermint_tpu.libs import fail
    from tendermint_tpu.libs.faults import faults

    def _reset_all():
        fail.reset()
        faults.reset()
        device_breaker.reset()
        reset_lane_breakers()
        phases.reset()
        phases.set_device_metrics(None)
        # only if a test built the multi-device pool: tear it down so the
        # next test re-resolves it (and re-reads its env knobs)
        md = sys.modules.get("tendermint_tpu.crypto.ed25519_jax.multidevice")
        if md is not None:
            md.reset_pool()
        # scheme registry + BLS caches are likewise process-global; only
        # touch them if a test actually imported those modules
        sch = sys.modules.get("tendermint_tpu.crypto.schemes")
        if sch is not None:
            sch.reset()
        bls = sys.modules.get("tendermint_tpu.crypto.bls12381")
        if bls is not None:
            bls.reset()
        bvec = sys.modules.get("tendermint_tpu.crypto.bls12381.vec")
        if bvec is not None:
            bvec.reset_stats()

    _reset_all()
    yield
    _reset_all()


def pytest_configure(config):
    # xdist's loadfile would hand the files out by their number of tests,
    # most first: the build files, small ones, would start late and two of
    # them could queue on one worker. The order below is the point.
    if hasattr(config.option, "loadscopereorder"):
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    # the same order in every xdist worker: a stable sort on the file name
    first = {name: i for i, name in enumerate(BUILD_FILES)}
    items.sort(key=lambda it: first.get(os.path.basename(str(it.fspath)),
                                        len(first)))
    # With the CPU pin disabled, only the on-device suite may run — anything
    # else would silently run on the chip (and assume 8 devices).
    if ON_DEVICE:
        skip = pytest.mark.skip(reason="TM_ON_DEVICE=1 runs only tests/test_tpu_device.py")
        for item in items:
            if "test_tpu_device" not in str(item.fspath):
                item.add_marker(skip)


if not ON_DEVICE:
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla_flags:
        os.environ["XLA_FLAGS"] = (
            xla_flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    # A persistent compile cache makes a REPEAT run fast; the driver's runs
    # are first runs (.jax_cache/ is not committed, and its keys move with
    # the kernel's graph), so the suite is sized for an empty cache: the
    # BUILD_FILES above and nothing else pay a whole-program build. One rule
    # for the cache's place (libs/compilecache.py): JAX_COMPILATION_CACHE_DIR
    # where set, else <checkout>/.jax_cache.
    from tendermint_tpu.libs.compilecache import enable_compile_cache

    enable_compile_cache()
    assert jax.default_backend() == "cpu", (
        "CPU pin failed: suite would silently run on "
        f"{jax.default_backend()!r}; a jax backend was initialized before "
        "conftest ran"
    )
    assert len(jax.devices()) == 8, (
        f"expected 8 virtual CPU devices, got {len(jax.devices())}"
    )
    # sharded.py binds verify._verify_kernel by name when first imported:
    # now, before any test stands the kernel in, or a later test of the
    # same process would find the stand-in inside the mesh program
    import tendermint_tpu.crypto.ed25519_jax.sharded  # noqa: E402,F401
