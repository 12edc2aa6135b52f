"""Serving runtime: continuous batching correctness + energy accounting."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.profiles import H100_LLAMA70B
from repro.core.workloads import AZURE
from repro.models import model as M
from repro.serving import (ContextRouter, EnergyMeter, PoolEngine, Request,
                           RouterPolicy, synthetic_requests)


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("yi-6b").reduced()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new):
    """Single-request greedy generation via repeated full forward."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _ = M.forward(params, cfg,
                              {"tokens": jnp.asarray([toks])})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_engine_matches_sequential_generation(small_model):
    """Continuous batching with interleaved requests must emit exactly the
    tokens that isolated greedy decoding emits."""
    cfg, params = small_model
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=int(n))
               for n in (5, 9, 3, 12)]
    eng = PoolEngine(cfg, params, window=64, profile=H100_LLAMA70B,
                     n_slots=2, name="t")   # 2 slots, 4 reqs -> queueing
    reqs = [Request(rid=i, prompt=p, max_new_tokens=6)
            for i, p in enumerate(prompts)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained(max_iters=500)
    assert len(eng.completed) == 4
    for r, p in zip(reqs, prompts):
        expect = _greedy_reference(cfg, params, list(map(int, p)), 6)
        assert r.generated[:6] == expect, (r.rid, r.generated, expect)


def test_nmax_admission(small_model):
    cfg, params = small_model
    eng = PoolEngine(cfg, params, window=32, profile=H100_LLAMA70B,
                     n_slots=3)
    reqs = synthetic_requests(AZURE, 8, cfg.vocab, seed=1, max_total=24)
    for r in reqs:
        r.max_new_tokens = min(r.max_new_tokens, 4)
        eng.submit(r)
    eng._admit()
    assert eng.n_active <= 3           # Eq. 3 ceiling enforced
    eng.run_until_drained(max_iters=500)
    assert len(eng.completed) == 8


def test_energy_meter_matches_eq2():
    """Charging N decode iterations at fixed (n, L) must converge to the
    analytical Eq. 2 tok/W — the serving system realises the paper's law."""
    prof = H100_LLAMA70B
    m = EnergyMeter(prof)
    n, L = 64, 8192
    for _ in range(500):
        m.charge_decode_step(n, L)
    assert m.tok_per_watt == pytest.approx(prof.tok_per_watt(n, L), rel=1e-6)


def test_router_policies(small_model):
    cfg, params = small_model
    mk = lambda: {
        "short": PoolEngine(cfg, params, window=32, profile=H100_LLAMA70B,
                            n_slots=2, name="short"),
        "long": PoolEngine(cfg, params, window=128, profile=H100_LLAMA70B,
                           n_slots=2, name="long")}
    r_fo = ContextRouter(mk(), RouterPolicy(
        ladder=[("short", 32.0), ("long", math.inf)]))
    short_req = Request(rid=0, prompt=np.arange(10), max_new_tokens=8)
    long_req = Request(rid=1, prompt=np.arange(100), max_new_tokens=8)
    assert r_fo.route(short_req) == "short"     # 18 <= 2*16
    assert r_fo.route(long_req) == "long"
    r_tp = ContextRouter(mk(), RouterPolicy(
        ladder=[("short", 16.0), ("long", math.inf)], p99_output=10,
        metric_kind="prompt_plus_p99"))
    assert r_tp.route(Request(rid=2, prompt=np.arange(5),
                              max_new_tokens=8)) == "short"
    assert r_tp.route(Request(rid=3, prompt=np.arange(10),
                              max_new_tokens=8)) == "long"  # conservative


def test_two_pool_beats_homo_on_energy(small_model):
    """The paper's claim at miniature scale: context routing gives better
    fleet tok/W than a homogeneous long-window pool on mixed traffic."""
    cfg, params = small_model
    rng = np.random.default_rng(42)
    reqs = []
    for i in range(10):
        plen = 6 if i % 5 else 90           # 80% short, 20% long
        reqs.append(Request(rid=i, prompt=rng.integers(0, cfg.vocab, plen),
                            max_new_tokens=5))

    homo = ContextRouter(
        {"only": PoolEngine(cfg, params, window=128,
                            profile=H100_LLAMA70B, n_slots=4, name="only")},
        RouterPolicy(ladder=[("only", math.inf)]))
    rep_h = homo.run([dataclasses.replace(r) for r in reqs], max_iters=500)

    routed = ContextRouter(
        {"short": PoolEngine(cfg, params, window=16,
                             profile=H100_LLAMA70B, n_slots=16, name="short"),
         "long": PoolEngine(cfg, params, window=128,
                            profile=H100_LLAMA70B, n_slots=4, name="long")},
        RouterPolicy(ladder=[("short", 16.0), ("long", math.inf)]))
    rep_r = routed.run([dataclasses.replace(r) for r in reqs], max_iters=500)

    assert rep_r["fleet"]["tok_per_watt"] > rep_h["fleet"]["tok_per_watt"]
