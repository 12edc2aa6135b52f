"""Context-length router — the paper's technique as a serving-layer feature.

`ContextRouter` fronts a set of PoolEngines and routes each request through
an **ordered admission ladder**: (role, boundary) pairs with strictly
ascending boundaries, the last infinite.  A request goes to the first role
whose boundary covers its routing metric.  The three §4 topologies and the
§10.3 K >= 3 generalisation are all instances of the ladder:

  homo      — [(only, inf)]: one pool, the long window.
  two_pool  — [(short, B_short), (long, inf)] on the conservative metric
              prompt + p99(output) (no overflow handling).
  fleetopt  — [(short, gamma * B_short), (long, inf)] on predicted total;
              the short pool serves window gamma * B_short.
  multipool — explicit K-entry ladder (core.multipool): K geometric
              windows, admission at window/gamma, per-hop overflow
              migration pool i -> pool i+1 (serving.fleetsim).
  disagg / disagg_fleetopt — prefill/decode disaggregation (core.disagg):
              an explicit ladder over the *prefill* roles only — every
              request enters through a prefill pool; the paired decode
              pools are fed exclusively by the KV-handoff hop inside
              serving.fleetsim, never by admission.
  moe_pool  — one pool, the long window, served by an MoE whose profile
              streams active params + a dispatch floor (core.moe); the
              ladder itself is the homo single rung.
  semantic / semantic_fleetopt / moe_semantic — §5.1 model-heterogeneous
              routing (`SemanticRouter`): a [small @ B_short, large @ inf]
              ladder where the rungs serve *different models*.  The
              classifier is the ladder metric (predicted total — a length
              proxy for task complexity) degraded by `misroute_rate`: each
              decision flips with that probability, deterministically per
              request id.  A true-short flipped large is just served
              inefficiently; a true-large flipped small is tagged
              `escalate_at = detect_tokens` — the small-model engine evicts
              it after that many decode tokens (quality detection) and
              FleetSim re-serves it from scratch in the large pool, its
              small-pool tokens backed out (never double-counted).
              `semantic_fleetopt` additionally gives the small pool
              FleetOpt overflow headroom (serve at gamma * B_short);
              `moe_semantic` binds the large rung to the MoE.

The router is what determines which segment of the logistic P(b) curve each
engine occupies — the mechanism behind the fleet-level 2.5x (paper §4.2).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

from repro.core.routing import ESCALATION_DETECT_TOKENS

from .request import Request

_HASH_A = 2654435761          # Knuth multiplicative hash (mod 2^32)


def _misroute_u(rid: int, seed: int) -> float:
    """Deterministic per-request uniform in [0, 1) for the misroute draw —
    a pure function of (rid, seed) so routing is order-independent and a
    misroute-rate sweep flips a *nested* set of requests (rate 0.1 misroutes
    a superset of rate 0.05), which is what makes the degradation sweep
    monotone rather than resampled noise."""
    return ((rid * _HASH_A + seed * 0x9E3779B9) % (1 << 32)) / float(1 << 32)


@dataclasses.dataclass
class RouterPolicy:
    # Ordered (role, admission boundary) ladder, compiled by
    # `TopologySpec.policy`: route to the first role whose boundary >= the
    # request's routing metric.
    ladder: List[Tuple[str, float]]
    p99_output: int = 1024     # conservative prompt_plus_p99 margin
    # routing metric: "predicted_total" (prompt + E[output]) or
    # "prompt_plus_p99" (prompt + p99_output — conservative two_pool)
    metric_kind: str = "predicted_total"
    # misroute channel: the (small, large) role pair the classifier's
    # decisions flip between; None disables flipping entirely
    flip: Optional[Tuple[str, str]] = None
    # classifier error rate, detection latency (decode tokens the small
    # model emits before a misroute escalates — the constant shared with
    # the analytical core.topospec semantic accounting so both layers
    # price the same latency) and the seed of the deterministic
    # per-request misroute draw
    misroute_rate: float = 0.0
    detect_tokens: int = ESCALATION_DETECT_TOKENS
    misroute_seed: int = 0
    # the TopologySpec this policy was compiled from (FleetSim reads pool
    # wiring — overflow/escalation/handoff edges — from it)
    spec: Optional[object] = dataclasses.field(default=None, repr=False)

    def metric(self, req: Request) -> float:
        """The routing metric: predicted total for overflow-capable
        topologies; prompt + p99(output) for conservative two_pool."""
        if self.metric_kind == "prompt_plus_p99":
            return req.prompt_len + self.p99_output
        return req.predicted_total


class ContextRouter:
    """Routes requests over anything pool-shaped: a scalar `PoolEngine`,
    a whole `PoolGroup` (the fleet simulator's batched pool), or any
    object with submit / stats / measured_totals — the router only ever
    submits and aggregates."""

    def __init__(self, pools: Dict[str, object], policy: RouterPolicy):
        self.pools = pools
        self.policy = policy
        ladder = list(policy.ladder)
        if not ladder:
            raise ValueError("RouterPolicy.ladder is empty: it needs at"
                             " least one (role, boundary) rung")
        missing = [r for r, _ in ladder if r not in pools]
        assert not missing, (missing, sorted(pools))
        bounds = [b for _, b in ladder]
        assert all(a < b for a, b in zip(bounds, bounds[1:])), \
            f"admission boundaries must be strictly ascending: {ladder}"
        assert math.isinf(bounds[-1]), \
            f"last ladder entry must admit everything: {ladder}"
        self.ladder = ladder

    def route(self, req: Request) -> str:
        m = self.policy.metric(req)
        for name, boundary in self.ladder:
            if m <= boundary:
                name = self._semantic_flip(req, name)
                self.pools[name].submit(req)
                return name
        raise AssertionError(
            f"no ladder entry admits metric {m}: {self.ladder}")

    def _semantic_flip(self, req: Request, nominal: str) -> str:
        """SemanticRouter error channel: flip the classifier's decision
        with probability `misroute_rate` (deterministic per request).  A
        true-large request flipped into the small-model pool is tagged for
        escalation after `detect_tokens` of decode; a true-short flipped
        large just rides the big model."""
        pol = self.policy
        if not (pol.flip is not None and pol.misroute_rate > 0.0):
            return nominal
        if _misroute_u(req.rid, pol.misroute_seed) >= pol.misroute_rate:
            return nominal
        small, large = pol.flip
        if nominal not in (small, large):
            return nominal
        req.misrouted = True
        if nominal == large:
            req.escalate_at = pol.detect_tokens
            return small
        return large

    def run(self, requests: List[Request], *, max_iters: int = 100_000
            ) -> Dict[str, dict]:
        """Route every request, drain every pool, report.  A pool that is
        still busy at `max_iters` raises `serving.DrainTruncatedError`
        (propagated, never swallowed): a truncated drain would roll
        under-counted tokens/energy straight into the fleet tok/W."""
        for r in requests:
            self.route(r)
        for eng in self.pools.values():
            eng.run_until_drained(max_iters=max_iters)
        return self.report()

    def report(self) -> Dict[str, dict]:
        """Per-pool stats + fleet roll-up.  The fleet tok/W honours each
        meter's steady-state measurement window (the windowed `m_*`
        counters) so it agrees with FleetSim.report on identical runs; with
        the default (0, inf) window the `m_*` counters mirror the lifetime
        totals and nothing changes for standalone engines."""
        out = {name: eng.stats() for name, eng in self.pools.items()}
        totals = [eng.measured_totals() for eng in self.pools.values()]
        tot_tok = sum(t["tokens"] for t in totals)
        tot_j = sum(t["joules"] for t in totals)
        out["fleet"] = dict(tokens=tot_tok, joules=round(tot_j, 1),
                            tok_per_watt=round(tot_tok / tot_j, 3)
                            if tot_j else 0.0)
        return out
