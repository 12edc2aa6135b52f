"""Serving launcher: context-length-routed pools over a real model.

Runs the paper's technique end-to-end at CPU demo scale: requests drawn
from a reconstructed trace are routed (homo / two_pool / fleetopt) into
continuous-batching PoolEngines; every decode iteration is charged
P(b) * tau, and the fleet report compares measured tok/W across topologies
— the Table-3 experiment as an executing system.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 24
"""
from __future__ import annotations

import argparse
import json
import math

import jax
import numpy as np

from repro.configs import get_config
from repro.core.profiles import H100_LLAMA70B
from repro.core.workloads import WORKLOADS
from repro.models.compat import enable_compile_cache
from repro.models import model as M
from repro.serving import (ContextRouter, PoolEngine, RouterPolicy,
                           synthetic_requests)


def build_router(cfg, params, policy: str, *, b_short: int, window_long: int,
                 profile, p99_output: int = 8) -> ContextRouter:
    if policy == "homo":
        pools = {"long": PoolEngine(cfg, params, window=window_long,
                                    profile=profile, n_slots=4, name="long")}
        return ContextRouter(pools, RouterPolicy(ladder=[("long", math.inf)]))
    pools = {
        "short": PoolEngine(cfg, params, window=2 * b_short, profile=profile,
                            n_slots=16, name="short"),
        "long": PoolEngine(cfg, params, window=window_long, profile=profile,
                           n_slots=4, name="long"),
    }
    # explicit admission ladders (the TopologySpec compilation of each
    # legacy kind): two_pool admits at b_short on the conservative
    # prompt + p99 metric; fleetopt at gamma * b_short on predicted total
    boundary = float(b_short) if policy == "two_pool" \
        else float(int(2.0 * b_short))
    return ContextRouter(pools, RouterPolicy(
        ladder=[("short", boundary), ("long", math.inf)],
        p99_output=p99_output,
        metric_kind="prompt_plus_p99" if policy == "two_pool"
        else "predicted_total"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--workload", default="azure-conv",
                    choices=list(WORKLOADS))
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--b-short", type=int, default=24)
    ap.add_argument("--window-long", type=int, default=192)
    ap.add_argument("--policies", default="homo,two_pool,fleetopt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    wl = WORKLOADS[args.workload]
    # draw raw trace lengths, then scale the whole distribution into the
    # demo windows (scaling preserves the short/long mix; clipping doesn't)
    lens = wl.sample_requests(args.requests, seed=0).astype(float)
    scale = (args.window_long - 8) / float(np.quantile(lens.sum(1), 0.99))
    rng = np.random.default_rng(7)
    base = []
    from repro.serving import Request
    for i, (p, o) in enumerate(lens * scale):
        p = int(np.clip(p, 1, args.window_long - 9))
        o = int(np.clip(o, 1, args.window_long - 8 - p))
        base.append(Request(rid=i,
                            prompt=rng.integers(0, cfg.vocab, size=p),
                            max_new_tokens=o))

    p99_out = int(np.quantile([r.max_new_tokens for r in base], 0.99)) + 1
    results = {}
    for policy in args.policies.split(","):
        import copy
        reqs = copy.deepcopy(base)
        router = build_router(cfg, params, policy, b_short=args.b_short,
                              window_long=args.window_long,
                              profile=H100_LLAMA70B, p99_output=p99_out)
        rep = router.run(reqs, max_iters=20000)
        results[policy] = rep
        print(f"\n== {policy} ==")
        for name, stats in rep.items():
            print(" ", name, json.dumps(stats))
    if {"homo", "fleetopt"} <= results.keys():
        gain = (results["fleetopt"]["fleet"]["tok_per_watt"]
                / results["homo"]["fleet"]["tok_per_watt"])
        print(f"\nFleetOpt vs Homo tok/W gain: {gain:.2f}x "
              "(paper fleet-scale: ~2.5x)")


if __name__ == "__main__":
    main()
