"""Serving-engine step timing + simulated fleet tok/W on the CPU demo."""
import math
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.profiles import H100_LLAMA70B
from repro.models import model as M
from repro.serving import ContextRouter, PoolEngine, Request, RouterPolicy


def run():
    cfg = get_config("yi-6b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    eng = PoolEngine(cfg, params, window=64, profile=H100_LLAMA70B,
                     n_slots=8, name="bench")
    for i in range(8):
        eng.submit(Request(rid=i, prompt=rng.integers(0, cfg.vocab, 12),
                           max_new_tokens=40))
    eng._admit()
    eng.step()  # compile
    t0 = time.perf_counter()
    iters = 20
    for _ in range(iters):
        eng.step()
    us = (time.perf_counter() - t0) / iters * 1e6
    rows = [dict(name="engine_step_b8_w64", us_per_call=round(us, 1),
                 derived=f"analytic_tok_per_watt={eng.meter.tok_per_watt:.3f}")]

    # two-pool routed mini-fleet
    pools = {
        "short": PoolEngine(cfg, params, window=32, profile=H100_LLAMA70B,
                            n_slots=8, name="short"),
        "long": PoolEngine(cfg, params, window=128, profile=H100_LLAMA70B,
                           n_slots=2, name="long")}
    router = ContextRouter(pools, RouterPolicy(
        ladder=[("short", 32.0), ("long", math.inf)]))
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab,
                                               6 if i % 4 else 90),
                    max_new_tokens=6) for i in range(12)]
    t0 = time.perf_counter()
    rep = router.run(reqs, max_iters=2000)
    wall = time.perf_counter() - t0
    rows.append(dict(name="routed_fleet_12req",
                     us_per_call=round(wall * 1e6, 0),
                     derived=f"fleet_tok_per_watt={rep['fleet']['tok_per_watt']}"))
    return rows, "serving engine operational"
