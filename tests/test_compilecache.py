"""libs/compilecache.py: the persistent-XLA-cache host fingerprint. A cache
dir built on a machine with different CPU features must produce a loud
startup warning (the cpu_aot_loader SIGILL footgun was previously buried in
stderr), and the outcome must be visible to debugdump via status(). Plus the
one rule for where the cache lives (enable_compile_cache)."""

import json
import os

import pytest

from tendermint_tpu.libs import compilecache as cc


def test_marker_written_then_matches(tmp_path):
    d = str(tmp_path / "cache")
    assert cc.check_cache_dir(d) is None  # first use: stamps the dir
    marker = os.path.join(d, cc.MARKER_NAME)
    assert os.path.exists(marker)
    doc = json.load(open(marker))
    fp = cc.host_fingerprint()
    assert doc["machine"] == fp["machine"]
    assert doc["flags_sha256"] == fp["flags_sha256"]
    # second process on the same host: clean
    assert cc.check_cache_dir(d) is None
    st = cc.status()
    assert st["cache_dir"] == d and st["mismatch"] is None


def test_foreign_cache_warns_sigill(tmp_path):
    d = str(tmp_path / "cache")
    os.makedirs(d)
    with open(os.path.join(d, cc.MARKER_NAME), "w") as f:
        json.dump({"machine": "imaginary-tpu-vm",
                   "flags_sha256": "deadbeef" * 8, "n_flags": 1}, f)
    warn = cc.check_cache_dir(d)
    assert warn is not None
    assert "SIGILL" in warn and "cpu_aot_loader" in warn
    assert "imaginary-tpu-vm" in warn
    assert cc.status()["mismatch"] == warn
    # the stale marker is NOT silently rewritten: every process on this
    # host keeps warning until the operator clears the cache dir
    assert cc.check_cache_dir(d) is not None


def test_preexisting_markerless_cache_warns_once_then_stamps(tmp_path):
    """A cache dir that already holds entries but no fingerprint (built
    before this feature, or copied from another machine) warns ONCE with
    the SIGILL wording, records the unverifiable origin in the marker, and
    goes quiet afterwards — a cache genuinely built on this host doesn't
    cry wolf forever, and a copied one still got its loud warning."""
    d = str(tmp_path / "cache")
    os.makedirs(d)
    open(os.path.join(d, "jit_foo-abc123-cache"), "w").write("x")
    warn = cc.check_cache_dir(d)
    assert warn is not None and "SIGILL" in warn
    marker = json.load(open(os.path.join(d, cc.MARKER_NAME)))
    assert marker["origin"] == "preexisting-unverified"
    assert cc.check_cache_dir(d) is None  # now fingerprint-matched


def test_torn_marker_restamps_instead_of_going_silent(tmp_path):
    """A half-written marker (concurrent first-start stampede on a shared
    cache dir) must not disable the check forever: it re-stamps as
    unverifiable origin — with the one-time warning — and then matches."""
    d = str(tmp_path / "cache")
    os.makedirs(d)
    open(os.path.join(d, "jit_foo-abc-cache"), "w").write("x")
    open(os.path.join(d, cc.MARKER_NAME), "w").write('{"machine": "tru')
    warn = cc.check_cache_dir(d)
    assert warn is not None and "SIGILL" in warn
    marker = json.load(open(os.path.join(d, cc.MARKER_NAME)))
    assert marker["origin"] == "preexisting-unverified"
    assert cc.check_cache_dir(d) is None


def test_fresh_dir_stamps_silently(tmp_path):
    d = str(tmp_path / "cache")
    assert cc.check_cache_dir(d) is None
    marker = json.load(open(os.path.join(d, cc.MARKER_NAME)))
    assert marker["origin"] == "fresh"


def test_unwritable_dir_degrades_to_no_warning(tmp_path):
    target = tmp_path / "file-not-dir"
    target.write_text("x")  # makedirs/marker write will fail
    assert cc.check_cache_dir(str(target)) is None  # advisory only


@pytest.fixture
def jax_cache_config():
    """The suite's shared cache must keep serving later tests."""
    import jax

    old = jax.config.jax_compilation_cache_dir
    try:
        yield jax.config
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


@pytest.mark.parametrize("case", ["placed_by_env", "default_is_checkout",
                                  "private_knob_ignored"])
def test_enable_compile_cache_configures_jax(case, tmp_path, monkeypatch,
                                             jax_cache_config):
    """The one rule: JAX_COMPILATION_CACHE_DIR where set (jax's own handling
    stands, no directory is set in code), else <checkout>/.jax_cache — a
    fixed path, whatever a node's --home is. TMTPU_JAX_CACHE is gone."""
    import jax

    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    placed = str(tmp_path / "placed")
    sentinel = str(tmp_path / "what-jax-read-at-import")
    jax.config.update("jax_compilation_cache_dir", sentinel)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("TMTPU_JAX_CACHE", raising=False)
    if case == "placed_by_env":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
        assert cc.enable_compile_cache() is None
        # untouched: jax read the variable itself when it was imported
        assert jax.config.jax_compilation_cache_dir == sentinel
        assert cc.status()["cache_dir"] == placed
        assert os.path.exists(os.path.join(placed, cc.MARKER_NAME))
        return
    if case == "private_knob_ignored":
        monkeypatch.setenv("TMTPU_JAX_CACHE", placed)
    cc.enable_compile_cache()
    assert cc.default_cache_dir() == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == cc.default_cache_dir()
    assert not os.path.exists(placed)


def test_node_home_does_not_move_the_cache(tmp_path, monkeypatch,
                                           jax_cache_config):
    """`cmd start` on a temp home (every harness) lands on the checkout's
    cache: a cache under the home would never hit."""
    import argparse

    from tendermint_tpu import cmd

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    home = str(tmp_path / "home")
    assert cmd.main(["--home", home, "init", "--chain-id", "cc"]) == 0
    node = cmd.build_node(argparse.Namespace(
        home=home, p2p_laddr="", rpc_laddr="", persistent_peers="",
        proxy_app=""))
    node.proxy_app.stop()
    import jax

    assert jax.config.jax_compilation_cache_dir == cc.default_cache_dir()
    assert not os.path.exists(os.path.join(home, ".jax_cache"))


def test_accelerator_process_does_not_cry_sigill(tmp_path, monkeypatch,
                                                 jax_cache_config):
    """A checkout copied to a machine with a chip carries the sandbox's
    stamp. A process that is not CPU-pinned reads and writes accelerator
    programs, not XLA:CPU AOT code: no warning, stamp left alone."""
    d = str(tmp_path / "copied")
    os.makedirs(d)
    foreign = {"machine": "x86_64", "flags_sha256": "0" * 64, "n_flags": 1}
    with open(os.path.join(d, cc.MARKER_NAME), "w") as f:
        json.dump(foreign, f)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    monkeypatch.setattr(cc, "_pinned_to_cpu", lambda: False)
    assert cc.enable_compile_cache() is None
    assert json.load(open(os.path.join(d, cc.MARKER_NAME))) == foreign
    monkeypatch.setattr(cc, "_pinned_to_cpu", lambda: True)
    assert "SIGILL" in cc.enable_compile_cache()
