"""`correct` has to come out false when the timed path is broken, and when
the reference's control answers in the program's place.

These skip the harness's look for a chip (``run.py`` does that) and drive
the rest of a run, ``harness.run_cell``, at the rehearsal's tiny sizes on
the CPU. The faults a cell of this system can have: an answer altered where
it is produced (both cells: the device's verdicts, the application's hash),
a step that returns its state unchanged (the sync cell's apply step), and,
for the guarantee the sync configuration states, a signature plane left
out (the window's light check, apply_block's LastCommit check) or answered
from an earlier sync. Batches and exchanges between chips do not exist in
these one-chip cells.
"""

import time

import pytest

import harness
import rehearse


def _run(workload, control=False, seed=77):
    return harness.run_cell(workload, seed, 1.0, False, time.perf_counter(),
                            overrides=rehearse.small_for(workload),
                            control=control)


#: read off BENCHMARK.json: a later PR's cell on a driver these faults
#: know joins them without an edit here
CELLS = harness.load_benchmark()["workloads"]
COMMIT_CELLS = [c["name"] for c in CELLS if harness.load_json(
    "traffic", c["traffic"] + ".json")["driver"] == "closed_loop_commits"]


@pytest.mark.parametrize("workload", [c["name"] for c in CELLS])
def test_sound_run_is_correct_and_control_is_not(workload):
    out = _run(workload)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    ctl = _run(workload, control=True)
    assert not ctl["correct"], ctl["compared"]


def _force_verdicts_true(monkeypatch):
    import numpy as np

    from tendermint_tpu.crypto import batch

    real = batch.BatchVerifier.verify

    def all_true(self):
        ok, per_item = real(self)
        return True, np.ones_like(per_item)

    monkeypatch.setattr(batch.BatchVerifier, "verify", all_true)


@pytest.mark.parametrize("workload", COMMIT_CELLS)
def test_commit_answer_altered_where_it_is_produced(workload, monkeypatch):
    """The device's verdicts all forced to True: the tampered commit is
    accepted, and the comparison with the reference has to say so."""
    _force_verdicts_true(monkeypatch)
    out = _run(workload)
    assert not out["correct"]
    assert out["compared"]["verdict_mismatches"]["value"] > 0


def test_sync_verdicts_altered_where_they_are_produced(monkeypatch):
    """The device's verdicts all forced to True (a kernel that answers
    all-true): the sound chain syncs to the same state, every route count
    reads nought, and only the tampered chains, followed to their end, show
    it."""
    _force_verdicts_true(monkeypatch)
    out = _run("sync1000.catchup")
    assert not out["correct"]
    assert out["compared"]["sync_state_mismatches"]["value"] == 2
    assert out["compared"]["signatures_not_on_device"]["value"] == 0


def test_sync_light_check_left_out(monkeypatch):
    """The window's light check answers "sound" unasked: the chain whose
    wrong row lies inside the 2/3 prefix is applied one block too far."""
    from tendermint_tpu.blockchain import reactor

    monkeypatch.setattr(reactor, "verify_commit_light_batched",
                        lambda entries: [None] * len(entries))
    out = _run("sync1000.catchup")
    assert not out["correct"]
    assert out["compared"]["sync_state_mismatches"]["value"] >= 1


def test_sync_last_commit_check_left_out(monkeypatch):
    """apply_block's full LastCommit check left out: the chain whose wrong
    row lies past the 2/3 prefix, which the light check cannot see, is
    followed to its end."""
    from tendermint_tpu.types import ValidatorSet

    monkeypatch.setattr(ValidatorSet, "verify_commit",
                        lambda self, *a, **kw: None)
    out = _run("sync1000.catchup")
    assert not out["correct"]
    assert out["compared"]["sync_state_mismatches"]["value"] >= 1


def test_sync_verdicts_served_from_an_earlier_sync(monkeypatch):
    """A verdict cache keyed on content across syncs: every answer is
    right, and the device verified the chain once instead of every time."""
    from tendermint_tpu.crypto import batch

    real = batch.BatchVerifier.verify
    kept = {}

    def cached(self):
        if batch.precomputed_verdicts.get() is not None:
            return real(self)       # a replay against verdicts already given
        key = (tuple(self._pks), tuple(self._msgs), tuple(self._sigs))
        if key in kept:
            self._pks, self._msgs, self._sigs = [], [], []
            return kept[key]
        kept[key] = real(self)
        return kept[key]

    monkeypatch.setattr(batch.BatchVerifier, "verify", cached)
    out = _run("sync1000.catchup")
    assert not out["correct"]
    assert out["compared"]["sync_state_mismatches"]["value"] == 0
    assert out["compared"]["signatures_not_on_device"]["value"] > 0


def test_sync_step_returns_its_state_unchanged(monkeypatch):
    """From the second sync on (the first is the warm-up, which the fault
    would stop before any window ran), the chain's last block is applied
    and its state thrown away."""
    from tendermint_tpu.state import BlockExecutor

    real = BlockExecutor.apply_block
    last = {"applied": 0}

    def unchanged(self, state, block_id, block, *a, **kw):
        new_state, retain = real(self, state, block_id, block, *a, **kw)
        if block.header.height == rehearse.SMALL["blocks"]:
            last["applied"] += 1
            if last["applied"] > 1:
                return state, retain
        return new_state, retain

    monkeypatch.setattr(BlockExecutor, "apply_block", unchanged)
    out = _run("sync1000.catchup")
    assert last["applied"] > 1
    assert not out["correct"]
    assert (out["failed"] > 0
            or out["compared"]["sync_state_mismatches"]["value"] > 0)


def test_sync_answer_altered_where_it_is_produced(monkeypatch):
    """The application reports another app hash at its last commit."""
    from tendermint_tpu.abci.example.kvstore import KVStoreApplication

    real = KVStoreApplication.commit

    def altered(self):
        resp = real(self)
        if self.height == rehearse.SMALL["blocks"]:
            self.app_hash = resp.data = b"\xff" * 8
        return resp

    monkeypatch.setattr(KVStoreApplication, "commit", altered)
    out = _run("sync1000.catchup")
    assert not out["correct"]
    assert out["compared"]["sync_state_mismatches"]["value"] > 0
