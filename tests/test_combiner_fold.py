"""The combiner's fold at dispatch and commit at the sender (DESIGN.md §4):
a fold counts toward the flush only once committed, the posted partial does
not depend on the order of commits, and a contribution folded ahead but
withdrawn before its commit leaves no trace in the posted partial."""
import queue

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quant as kq
from repro.serving.combiner import DeviceCombiner
from repro.serving.metrics import StageTimers
from repro.serving.segments import Request

ROWS, C = 8, 16
RULES = ["weighted", "vote", "pallas", "pallas_quant"]


def _req(rule):
    combine = "pallas" if rule == "pallas_quant" else rule
    return Request(0, np.zeros((ROWS, 4), np.int32), ROWS, C, ROWS, [0, 1],
                   {0: 0.6, 1: 0.4}, combine)


def _preds(rule, n=3, seed=0):
    """``n`` device predictions of one segment, as the members' steps
    emit them under ``rule``."""
    rng = np.random.default_rng(seed)
    P = [jnp.asarray(rng.normal(size=(ROWS, C)).astype(np.float32))
         for _ in range(n)]
    if rule == "pallas_quant":
        P = [kq.quantize_symmetric(p, axis=-1) for p in P]
    return P


def _combiner():
    q, timers = queue.Queue(), StageTimers()
    return DeviceCombiner("d0", q, timers=timers), q, timers


def _posted_alone(rule, contributions):
    """The partial a combiner posts when each (member, P) is folded and
    committed at once, one after another — the order the sender kept when
    it folded after its device wait."""
    comb, q, _ = _combiner()
    req = _req(rule)
    comb.begin(req, {0: len(contributions)})
    for m, P in contributions:
        comb.commit(req, 0, comb.add(req, 0, m, P))
    return q.get_nowait().P


@pytest.mark.parametrize("commit_order", [(0, 1), (1, 0)])
@pytest.mark.parametrize("rule", RULES)
def test_folds_commute_with_commits(rule, commit_order):
    P0, P1, _ = _preds(rule)
    comb, q, timers = _combiner()
    req = _req(rule)
    comb.begin(req, {0: 2})
    folds = [comb.add(req, 0, 0, P0), comb.add(req, 0, 1, P1)]
    assert q.empty()                      # folded ahead, nothing committed
    comb.commit(req, 0, folds[commit_order[0]])
    assert q.empty()
    comb.commit(req, 0, folds[commit_order[1]])
    msg = q.get_nowait()
    assert msg.count == 2 and comb.partials_posted == 1
    np.testing.assert_array_equal(msg.P,
                                  _posted_alone(rule, [(0, P0), (1, P1)]))
    assert timers.counters["folds_ahead"] == 2
    assert "fold_rebuilds" not in timers.counters
    assert not comb._parts and not comb._expected


def _quarantine_forgive(comb, req, P):
    # member 0's worker is quarantined after its fold: the supervisor wins
    # the ledger entry and, with no sibling, forgives the unit
    comb.add(req, 0, 0, P[0])
    comb.commit(req, 0, comb.add(req, 0, 1, P[1]))
    comb.unexpect(req, 0)
    return [(1, P[1])]


def _replay_to_sibling(comb, req, P):
    # member 0's unit is replayed to a sibling on the same device, which
    # shares the combiner: the quarantined worker's fold never commits
    comb.add(req, 0, 0, P[0])
    f1 = comb.add(req, 0, 1, P[1])
    f0 = comb.add(req, 0, 0, P[2])        # the sibling's replay
    comb.commit(req, 0, f1)
    comb.commit(req, 0, f0)
    return [(1, P[1]), (0, P[2])]


def _replay_nan_to_sibling(comb, req, P):
    # a poisoned chunk's host output is folded at dispatch; the NaN guard
    # stops its commit and the unit replays on a same-device sibling
    comb.add(req, 0, 0, np.full((ROWS, C), np.nan, np.float32))
    f1 = comb.add(req, 0, 1, P[1])
    f0 = comb.add(req, 0, 0, P[2])
    comb.commit(req, 0, f0)
    comb.commit(req, 0, f1)
    return [(1, P[1]), (0, P[2])]


def _demoted_after_dispatch(comb, req, P):
    # member 0 is demoted after its fold: its sender forgives the unit
    # before member 1 commits
    comb.add(req, 0, 0, P[0])
    f1 = comb.add(req, 0, 1, P[1])
    comb.unexpect(req, 0)
    comb.commit(req, 0, f1)
    return [(1, P[1])]


WITHDRAWALS = [_quarantine_forgive, _replay_to_sibling,
               _replay_nan_to_sibling, _demoted_after_dispatch]


@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("withdraw", WITHDRAWALS,
                         ids=lambda f: f.__name__[1:])
def test_a_withdrawn_fold_is_absent_from_the_posted_partial(withdraw, rule):
    P = _preds(rule)
    comb, q, timers = _combiner()
    req = _req(rule)
    comb.begin(req, {0: 2})
    committed = withdraw(comb, req, P)
    msg = q.get_nowait()
    assert q.empty() and msg.count == len(committed)
    np.testing.assert_array_equal(msg.P, _posted_alone(rule, committed))
    assert timers.counters["fold_rebuilds"] == 1
    assert not comb._parts and not comb._expected


def test_a_fold_after_the_post_or_teardown_is_ignored():
    P0, P1, P2 = _preds("weighted")
    comb, q, _ = _combiner()
    req = _req("weighted")
    comb.begin(req, {0: 2})
    stale = comb.add(req, 0, 0, P0)
    comb.commit(req, 0, comb.add(req, 0, 0, P2))
    comb.commit(req, 0, comb.add(req, 0, 1, P1))
    assert comb.partials_posted == 1
    # a quarantined worker waking up late: its fold finds no partial, and
    # the fold it made before the post commits nothing
    assert comb.add(req, 0, 0, P0) is None
    comb.commit(req, 0, stale)
    comb.begin(req, {0: 2})
    comb.add(req, 0, 0, P0)
    comb.finish(req.rid)                  # the request completed or failed
    comb.commit(req, 0, stale)
    assert comb.partials_posted == 1 and not comb._parts
