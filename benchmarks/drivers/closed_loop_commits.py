"""Driver: one caller, closed loop, ``ValidatorSet.verify_commit``.

A validator cannot start a height before the last commit is verified, so
the load is one request at a time: the next commit of a seeded pool, cycled.
A request is one ``verify_commit(chain_id, block_id, height, commit)`` call,
timed from the call to its verdict (or its exception) on the host.

Traffic parameters (the traffic file's keys):
    pool_commits       commits at successive heights, signed from the seed
    tampered_commits   how many of them carry one tampered signature
    tamper_past        [num, den]: the tampered row lies past the prefix
                       that holds num/den of the power (a seeded row there)
    check_sample       pool entries whose requests the reference checks
    first_height, chain_id
    trace_after_ticks, trace_ticks   a traced run starts the profiler after
                       that many requests and stops it that many later
"""

from __future__ import annotations

import numpy as np

import data as D
from objects import as_received


def _program_valset(vals: D.PlainValidators):
    from tendermint_tpu import crypto
    from tendermint_tpu.types import Validator, ValidatorSet

    pubs = [crypto.Ed25519PubKey(pk) for pk in vals.pubkeys]
    vs = ValidatorSet([Validator(p.address(), p, power)
                       for p, power in zip(pubs, vals.powers)])
    if [v.pub_key.bytes() for v in vs.validators] != vals.pubkeys:
        raise RuntimeError("the program orders the validator set otherwise "
                           "than the benchmark's data")
    return vs


def _program_commit(vs, commit: D.PlainCommit):
    from tendermint_tpu.types.basic import BlockID, BlockIDFlag, PartSetHeader
    from tendermint_tpu.types.block import Commit, CommitSig

    bid = BlockID(commit.block_id.hash,
                  PartSetHeader(commit.block_id.parts_total,
                                commit.block_id.parts_hash))
    sigs = [CommitSig(BlockIDFlag.COMMIT, v.address, ts, sig)
            for v, ts, sig in zip(vs.validators, commit.timestamps_ns,
                                  commit.signatures)]
    return Commit(commit.height, commit.round, bid, sigs)


def build(config: dict, traffic: dict, seed: int) -> dict:
    vals = D.make_validators(config["validators"], seed,
                             n_heavy=config["heavy_validators"],
                             heavy_power=config["heavy_power"],
                             power=config["power"])
    rng = np.random.default_rng([seed, 1])
    n_pool = traffic["pool_commits"]
    lo = D.first_row_past(vals, *traffic["tamper_past"])
    if lo >= len(vals):
        raise ValueError("no row lies past the tamper_past prefix")
    plain = [D.sign_commit(vals, traffic["chain_id"],
                           traffic["first_height"] + i)
             for i in range(n_pool)]
    # every seed the same mix, in another order
    for slot in rng.permutation(n_pool)[:traffic["tampered_commits"]]:
        plain[slot] = D.tamper(plain[slot], int(rng.integers(lo, len(vals))))
    vs = _program_valset(vals)
    return {"seed": seed, "traffic": traffic, "vals": vals, "plain": plain,
            "vs": vs, "commits": [_program_commit(vs, c) for c in plain]}


def _request(data: dict, commit) -> tuple:
    from tendermint_tpu.types.errors import (
        ErrNotEnoughVotingPowerSigned,
        ErrWrongSignature,
    )

    try:
        data["vs"].verify_commit(data["traffic"]["chain_id"],
                                 commit.block_id, commit.height, commit)
    except ErrWrongSignature as e:
        return ("wrong_signature", e.idx)
    except ErrNotEnoughVotingPowerSigned as e:
        return ("not_enough_power", e.got, e.needed)
    return ("accept",)


def warm(data: dict) -> None:
    """Two calls of the entry on pool data, an accepted and a refused one:
    the programs this size uses (at 10,000 validators the sparse stream
    pair, K=3 and K=2; at 150 the one-call 256-lane program) and both ways
    out."""
    slots = [next(i for i, c in enumerate(data["plain"])
                  if bool(c.tampered_rows) == bad) for bad in (False, True)]
    for slot in slots:
        _request(data, as_received(data["commits"][slot]))


def window(data: dict, seconds: float, probe) -> list:
    n_sigs = len(data["vals"])
    n_pool = len(data["commits"])
    requests, i = [], 0
    t_end = probe.clock() + seconds
    while True:
        slot = i % n_pool
        # the pool is cycled; a validator sees a commit once: a new Commit
        # over the same signatures, with nothing memoized on it (objects.py)
        commit = as_received(data["commits"][slot])
        with probe.span("request") as sp:
            try:
                answer, failed = _request(data, commit), False
            except Exception as e:  # recorded: an operation failed
                answer, failed = ("error", type(e).__name__, str(e)), True
        requests.append({"t0": sp["t0"], "t1": sp["t1"], "slot": slot,
                         "units": {"sigs": n_sigs}, "answer": answer,
                         "failed": failed})
        i += 1
        probe.tick()
        if probe.clock() >= t_end:
            return requests


def compare(data: dict, requests: list, reference, control: bool = False
            ) -> dict:
    """Every request of the window that used a sampled pool entry, against
    the plain reference's answer for that entry. The sample is drawn from
    the seed and holds every tampered entry."""
    rng = np.random.default_rng([data["seed"], 2])
    plain, vals = data["plain"], data["vals"]
    bad = [i for i, c in enumerate(plain) if c.tampered_rows]
    good = [i for i in range(len(plain)) if i not in bad]
    n_good = max(0, data["traffic"]["check_sample"] - len(bad))
    sample = bad + [int(i) for i in rng.permutation(good)[:n_good]]
    want = {i: reference.verify_commit(vals, plain[i]) for i in sample}
    got_control = ({i: reference.control(vals, plain[i]) for i in sample}
                   if control else None)
    checked = mismatches = 0
    for r in requests:
        if r["slot"] not in want:
            continue
        checked += 1
        got = got_control[r["slot"]] if control else r["answer"]
        mismatches += got != want[r["slot"]]
    seen = {r["slot"] for r in requests}
    return {"verdict_mismatches": (mismatches, 0),
            "tampered_entries_unseen": (sum(1 for i in bad if i not in seen),
                                        0),
            "requests_unchecked": (int(checked == 0), 0)}
