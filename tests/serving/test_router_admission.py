"""RouterPolicy admission boundaries — exact edges for all three §4
topologies plus the K >= 3 multipool ladder, against analytical-mode
engines (no model, no jax on the hot path)."""
import math

import numpy as np
import pytest

from repro.core.profiles import H100_LLAMA70B
from repro.serving import ContextRouter, PoolEngine, Request, RouterPolicy

STREAMED = 70e9


def _pool(name, window):
    return PoolEngine(None, None, window=window, profile=H100_LLAMA70B,
                      n_slots=4, name=name, streamed_params=STREAMED)


def _req(rid, plen, out, predicted=None):
    return Request(rid=rid, prompt=np.zeros(plen, np.int64),
                   max_new_tokens=out, predicted_output=predicted)


def _router(kind, *, b_short=4096, gamma=2.0, **kw):
    # explicit ladders, the TopologySpec.from_kind compilation of each
    # legacy kind (policies no longer derive rungs from the kind string)
    if kind == "homo":
        pools = {"only": _pool("only", 256)}
        ladder = [("only", math.inf)]
    else:
        pools = {"short": _pool("short", 64), "long": _pool("long", 256)}
        boundary = float(b_short) if kind == "two_pool" \
            else float(int(gamma * b_short))
        ladder = [("short", boundary), ("long", math.inf)]
    if kind == "two_pool":
        kw.setdefault("metric_kind", "prompt_plus_p99")
    return ContextRouter(pools, RouterPolicy(ladder=ladder, **kw))


def test_homo_routes_everything_to_the_single_pool():
    r = _router("homo", b_short=32)
    assert r.route(_req(0, 1, 1)) == "only"
    assert r.route(_req(1, 1000, 1000)) == "only"


def test_two_pool_admission_boundary_is_exact():
    # short iff prompt + p99_output <= b_short (conservative, no overflow)
    r = _router("two_pool", b_short=32, p99_output=10)
    assert r.route(_req(0, 22, 1)) == "short"      # 22 + 10 == 32, inclusive
    assert r.route(_req(1, 23, 1)) == "long"       # 23 + 10 == 33 > 32
    # actual output length is irrelevant: only the p99 margin counts
    assert r.route(_req(2, 22, 500)) == "short"


def test_two_pool_p99_margin_edge():
    # margin 0: boundary collapses to prompt_len <= b_short
    r = _router("two_pool", b_short=32, p99_output=0)
    assert r.route(_req(0, 32, 1)) == "short"
    assert r.route(_req(1, 33, 1)) == "long"


def test_fleetopt_admission_boundary_is_gamma_b_short():
    # short iff predicted_total <= gamma * b_short (overflow headroom)
    r = _router("fleetopt", b_short=32, gamma=2.0)
    assert r.route(_req(0, 54, 10)) == "short"     # 64 == 2 * 32, inclusive
    assert r.route(_req(1, 55, 10)) == "long"      # 65 > 64
    # gamma = 1: no headroom, boundary is b_short itself
    r1 = _router("fleetopt", b_short=32, gamma=1.0)
    assert r1.route(_req(2, 22, 10)) == "short"
    assert r1.route(_req(3, 23, 10)) == "long"


def test_fleetopt_routes_on_prediction_not_actual_length():
    """Honest routing: predicted_output (E[output]) drives the decision,
    not the sampled output length — the source of overflow migrations."""
    r = _router("fleetopt", b_short=32, gamma=2.0)
    # predicted 30 + 30 = 60 <= 64 -> short, though actual total is 530
    assert r.route(_req(0, 30, 500, predicted=30)) == "short"
    # predicted 30 + 40 = 70 > 64 -> long, though actual total is only 35
    assert r.route(_req(1, 30, 5, predicted=40)) == "long"


# --- K >= 3 admission ladders (paper §10.3 via core.multipool) -----------

def _k3_pools():
    return {"p0": _pool("p0", 128), "p1": _pool("p1", 512),
            "p2": _pool("p2", 2048)}


def _k3_router():
    ladder = [("p0", 64.0), ("p1", 256.0), ("p2", math.inf)]
    return ContextRouter(_k3_pools(), RouterPolicy(ladder=ladder))


def test_multipool_ladder_boundaries_are_exact():
    r = _k3_router()
    assert r.route(_req(0, 32, 32)) == "p0"     # 64 == boundary, inclusive
    assert r.route(_req(1, 33, 32)) == "p1"     # 65 > 64
    assert r.route(_req(2, 128, 128)) == "p1"   # 256 == boundary, inclusive
    assert r.route(_req(3, 129, 128)) == "p2"   # 257 > 256
    assert r.route(_req(4, 10_000, 1)) == "p2"  # terminal rung takes all


def test_multipool_routes_on_prediction_not_actual_length():
    r = _k3_router()
    # predicted 30 + 30 = 60 <= 64 -> p0, though the actual total is 530
    assert r.route(_req(0, 30, 500, predicted=30)) == "p0"
    assert r.route(_req(1, 30, 5, predicted=400)) == "p2"


class _Sink:
    """Pool-shaped stand-in that records the rids submitted to it."""

    def __init__(self):
        self.rids = []

    def submit(self, req):
        self.rids.append(req.rid)


def test_k3_ladder_routes_a_trace_to_the_first_admitting_rung():
    """Every request of a trace lands on the first rung whose boundary
    covers its predicted total — the kept ladder routes as a ladder
    re-read per request would."""
    rng = np.random.default_rng(7)
    bounds = [64.0, 256.0, math.inf]
    totals = np.concatenate([rng.integers(2, 600, 400), [64, 65, 256, 257]])
    reqs = []
    for rid, t in enumerate(totals):
        plen = int(rng.integers(1, t))
        reqs.append(_req(rid, plen, 1, predicted=int(t) - plen))
    sinks = {"p0": _Sink(), "p1": _Sink(), "p2": _Sink()}
    r = ContextRouter(sinks, RouterPolicy(ladder=list(zip(sinks, bounds))))
    got = [r.route(q) for q in reqs]
    want = [list(sinks)[i] for i in np.searchsorted(bounds, totals)]
    assert got == want
    assert {role: s.rids for role, s in sinks.items()} == {
        role: [i for i, w in enumerate(want) if w == role] for role in sinks}
    assert all(s.rids for s in sinks.values())


@pytest.mark.parametrize("ladder,exc", [(None, TypeError), ([], ValueError)],
                         ids=["missing", "empty"])
def test_multipool_policy_requires_ladder(ladder, exc):
    """A policy cannot be built without a ladder, and a router refuses an
    empty one when it is constructed, not at the first route."""
    with pytest.raises(exc):
        policy = RouterPolicy() if ladder is None \
            else RouterPolicy(ladder=ladder)
        ContextRouter({"p0": _pool("p0", 128)}, policy)


def test_ladder_must_ascend_and_terminate_infinite():
    pools = _k3_pools()
    with pytest.raises(AssertionError):   # descending boundaries
        ContextRouter(pools, RouterPolicy(
            ladder=[("p0", 256.0), ("p1", 64.0), ("p2", math.inf)]))
    with pytest.raises(AssertionError):   # last rung not infinite
        ContextRouter(pools, RouterPolicy(
            ladder=[("p0", 64.0), ("p1", 256.0)]))


def test_ladder_roles_must_exist():
    with pytest.raises(AssertionError):
        ContextRouter({"p0": _pool("p0", 128)},
                      RouterPolicy(ladder=[("nope", math.inf)]))
