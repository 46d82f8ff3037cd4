"""Test env: force JAX onto CPU with 8 virtual devices so sharding/multi-chip
paths are exercised without TPU hardware. Speed is measured on the chip
(``python chip_smoke.py``, the benchmark), never here.

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

import os

import pytest

ON_DEVICE = os.environ.get("TM_ON_DEVICE") == "1"


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


def pytest_collection_modifyitems(config, items):
    # With the CPU pin disabled, only the on-device suite may run — anything
    # else would silently run on the chip (and assume 8 devices).
    if ON_DEVICE:
        import pytest

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
    # The ed25519 verify kernel takes minutes to compile on CPU; a persistent
    # cache makes repeat suite runs fast (first run still pays the compiles).
    # One rule for its place (libs/compilecache.py): JAX_COMPILATION_CACHE_DIR
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
