"""Model-heterogeneous serving (DESIGN.md §9): the SemanticRouter's
misroute/escalation channel, per-role model bindings through
ModelProfileRegistry, the MoE dispatch-floor attribution, the
bandwidth-scaled prefill chunk, and the tentpole integration check —
measured semantic / MoE fleet tok/W within 25% of the analytical
core.routing.Semantic / core.moe provisioning at zero misroute and zero
dispatch.  Deterministic seeds; no jax."""
import math

import numpy as np
import pytest

from repro.core.hardware import H100
from repro.core.modelspec import (LLAMA31_8B, LLAMA31_70B, QWEN3_235B_A22B)
from repro.core.moe import moe_profile, with_dispatch_floor
from repro.core.power import H100_POWER
from repro.core.profiles import (B200_LLAMA70B_FLEET, H100_LLAMA70B,
                                 V5E_LLAMA70B)
from repro.core.topospec import TopologySpec
from repro.core.workloads import AZURE
from repro.serving import (ContextRouter, EnergyMeter, FleetSim, PoolEngine,
                           Request, RouterPolicy, scaled_prefill_chunk,
                           simulate_spec)

STREAMED = LLAMA31_70B.streamed_params


def _req(rid, plen, out, pred=None, t=0.0):
    return Request(rid=rid, prompt=np.zeros(plen, np.int64),
                   max_new_tokens=out, arrival_time=t,
                   predicted_output=pred)


def _pools():
    return {"small": PoolEngine(None, None, window=64,
                                profile=H100_LLAMA70B, n_slots=4,
                                streamed_params=LLAMA31_8B.streamed_params),
            "large": PoolEngine(None, None, window=4096,
                                profile=H100_LLAMA70B, n_slots=4,
                                streamed_params=STREAMED)}


# --- SemanticRouter misroute channel ------------------------------------

def test_semantic_routes_by_predicted_total_at_zero_misroute():
    r = ContextRouter(_pools(), RouterPolicy(
        ladder=[("small", 64.0), ("large", math.inf)],
        flip=("small", "large")))
    assert r.route(_req(0, 32, 500, pred=32)) == "small"   # 64, inclusive
    assert r.route(_req(1, 33, 1, pred=32)) == "large"     # 65 > 64
    # zero misroute never flips or tags
    for rid in range(50):
        req = _req(100 + rid, 10, 10, pred=10)
        r.route(req)
        assert not req.misrouted and req.escalate_at is None


def test_misroute_flip_tags_only_large_into_small():
    pol = RouterPolicy(ladder=[("small", 64.0), ("large", math.inf)],
                       misroute_rate=0.5, detect_tokens=7, misroute_seed=3,
                       flip=("small", "large"))
    r = ContextRouter(_pools(), pol)
    tagged = flipped_large = 0
    for rid in range(400):
        truly_large = rid % 2
        req = _req(rid, 100 if truly_large else 10, 10, pred=10)
        dest = r.route(req)
        if req.misrouted:
            if truly_large:           # large flipped into the small pool
                assert dest == "small"
                assert req.escalate_at == 7
                tagged += 1
            else:                     # short flipped large: no escalation
                assert dest == "large"
                assert req.escalate_at is None
                flipped_large += 1
        else:
            assert dest == ("large" if truly_large else "small")
            assert req.escalate_at is None
    # rate 0.5 over 200 per class: both directions actually exercised
    assert tagged > 50 and flipped_large > 50


def test_misroute_draw_is_deterministic_and_nested():
    """The per-request uniform is a pure function of (rid, seed), so a
    higher misroute rate flips a *superset* of a lower rate's requests —
    the property that makes the degradation sweep monotone."""
    def misrouted(rate):
        pol = RouterPolicy(ladder=[("small", 64.0), ("large", math.inf)],
                           misroute_rate=rate, flip=("small", "large"))
        r = ContextRouter(_pools(), pol)
        out = set()
        for rid in range(500):
            req = _req(rid, 10, 10, pred=10)
            r.route(req)
            if req.misrouted:
                out.add(rid)
        return out

    lo, hi = misrouted(0.1), misrouted(0.3)
    assert misrouted(0.1) == lo          # deterministic
    assert lo < hi                       # strictly nested


# --- engine escalation eviction -----------------------------------------

def test_engine_escalates_after_detect_tokens_and_backs_out():
    eng = PoolEngine(None, None, window=4096, profile=H100_LLAMA70B,
                     n_slots=1, streamed_params=STREAMED)
    req = _req(0, 8, 100)
    req.escalate_at = 4
    eng.submit(req)
    eng.run_until_drained(max_iters=50)
    assert len(eng.completed) == 0
    assert len(eng.escalated) == 1 and eng.n_escalated == 1
    assert req.escalations == 1 and req.preemptions == 1
    assert req.escalate_at is None       # detected once, never re-tagged
    assert req.ready_time is not None and not req.prefill_done
    # the 3 wasted decode tokens are backed out; the energy stays
    assert eng.meter.tokens == 0
    assert eng.meter.joules > 0


def test_short_output_completes_before_detection():
    """A misrouted request whose output ends under the detection latency
    simply finishes in the small pool — quality review never fires."""
    eng = PoolEngine(None, None, window=4096, profile=H100_LLAMA70B,
                     n_slots=1, streamed_params=STREAMED)
    req = _req(0, 8, 3)
    req.escalate_at = 32
    eng.submit(req)
    eng.run_until_drained(max_iters=50)
    assert len(eng.completed) == 1 and not eng.escalated
    assert req.escalations == 0


def test_overflow_eviction_clears_escalation_tag():
    """A misrouted giant prompt that hits the window ceiling before the
    quality monitor fires leaves through the overflow channel — and must
    not re-escalate out of the large pool it lands in."""
    eng = PoolEngine(None, None, window=16, profile=H100_LLAMA70B,
                     n_slots=1, streamed_params=STREAMED,
                     evict_on_overflow=True)
    req = _req(0, 14, 500)
    req.escalate_at = 32
    eng.submit(req)
    eng.run_until_drained(max_iters=50)
    (evicted,) = eng.overflowed
    assert evicted.escalate_at is None
    assert not eng.escalated


# --- ModelProfileRegistry wiring ----------------------------------------

def test_spec_build_binds_models_per_role():
    policy, plan, registry = TopologySpec.from_kind(
        "semantic", H100_LLAMA70B, LLAMA31_70B, b_short=4096).build(AZURE)
    assert registry.for_role("small").model is LLAMA31_8B
    assert registry.for_role("large").model is LLAMA31_70B
    assert registry.heterogeneous
    small, large = sorted(plan.pools, key=lambda p: p.window)
    assert small.window == 4096          # semantic: no overflow headroom
    assert small.profile is not large.profile
    sim = FleetSim(policy, plan, registry=registry)
    assert sim.escalate_to == {"small": "large"}
    assert sim.overflow_to == {"small": "large"}
    # each pool's engines stream their own model's bytes
    assert sim.groups["small"].streamed_params \
        == LLAMA31_8B.streamed_params
    assert sim.groups["large"].streamed_params \
        == LLAMA31_70B.streamed_params


def test_semantic_fleetopt_gets_overflow_headroom():
    _, plan, _ = TopologySpec.from_kind(
        "semantic_fleetopt", H100_LLAMA70B, LLAMA31_70B, b_short=4096,
        gamma=2.0).build(AZURE)
    small = min(plan.pools, key=lambda p: p.window)
    assert small.window == 8192          # serve at gamma * b_short


def test_misroute_and_dispatch_args_are_kind_checked():
    with pytest.raises(ValueError):
        TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                               misroute_rate=0.1)
    with pytest.raises(ValueError):
        TopologySpec.from_kind("semantic", H100_LLAMA70B, LLAMA31_70B,
                               dispatch_ms=2.0)


# --- MoE dispatch floor --------------------------------------------------

def test_with_dispatch_floor_extends_tau_and_meter_attributes_it():
    prof = moe_profile(QWEN3_235B_A22B, H100, H100_POWER, tp=8)
    prof_d = with_dispatch_floor(prof, 10.0)
    assert prof_d.roofline.w_ms == pytest.approx(prof.roofline.w_ms + 10.0)
    m = EnergyMeter(prof_d)
    m.dispatch_s = 10e-3
    tau = m.charge_decode_step(4, 2048.0)
    assert tau > 10e-3                   # the floor is inside tau
    power = prof_d.power_w(4)
    assert m.dispatch_joules == pytest.approx(power * 10e-3)
    assert m.dispatch_joules < m.joules  # attribution, never extra energy


def test_moe_pool_engines_stream_active_params():
    prof = moe_profile(QWEN3_235B_A22B, H100, H100_POWER, tp=8)
    policy, plan, registry = TopologySpec.from_kind(
        "moe_pool", prof, QWEN3_235B_A22B, dispatch_ms=2.0).build(AZURE)
    assert registry.default.dispatch_ms == 2.0
    (pool,) = plan.pools
    assert pool.profile.roofline.w_ms == pytest.approx(
        prof.roofline.w_ms + 2.0)
    sim = FleetSim(policy, plan, registry=registry)
    grp = sim.groups["moe"]
    assert grp.streamed_params == QWEN3_235B_A22B.n_active_params
    assert grp.dispatch_s == pytest.approx(2e-3)


# --- bandwidth-scaled prefill chunk -------------------------------------

def test_prefill_chunk_scales_with_memory_bandwidth():
    assert scaled_prefill_chunk(H100_LLAMA70B, 512) == 512
    assert scaled_prefill_chunk(B200_LLAMA70B_FLEET, 512) == \
        round(512 * 8.0e12 / 3.35e12)
    # slow chips scale down but never below the floor
    assert scaled_prefill_chunk(V5E_LLAMA70B, 512) == \
        max(round(512 * 819e9 / 3.35e12), 64)
    assert scaled_prefill_chunk(V5E_LLAMA70B, 100, floor=64) == 64


def test_fleetsim_applies_scaled_chunk_per_pool():
    policy, plan, registry = TopologySpec.from_kind(
        "homo", B200_LLAMA70B_FLEET, LLAMA31_70B).build(AZURE)
    sim = FleetSim(policy, plan, registry=registry, prefill_chunk=512)
    assert sim.groups["homo"].prefill_chunk == \
        scaled_prefill_chunk(B200_LLAMA70B_FLEET, 512)


# --- fleet-level integration (the tentpole acceptance) ------------------

@pytest.fixture(scope="module")
def hetero_cells():
    prof_moe = moe_profile(QWEN3_235B_A22B, H100, H100_POWER, tp=8)
    cells = {kind: simulate_spec(
        TopologySpec.from_kind(kind, H100_LLAMA70B, LLAMA31_70B,
                               b_short=4096, misroute_seed=0),
        AZURE, n_requests=8000, seed=0)
        for kind in ("semantic", "semantic_fleetopt")}
    cells["moe_pool"] = simulate_spec(
        TopologySpec.from_kind("moe_pool", prof_moe, QWEN3_235B_A22B),
        AZURE, n_requests=8000, seed=0)
    return cells


def test_measured_within_tolerance_of_analytical(hetero_cells):
    """Acceptance gate: measured decode tok/W within 25% of the
    analytical core.routing.Semantic / core.moe provisioning at zero
    misroute and zero dispatch (observed at seed 0 / 8k requests:
    semantic -8%, semantic_fleetopt -7%, moe_pool -12%)."""
    for kind, cell in hetero_cells.items():
        assert abs(cell.delta_pct) < 25.0, (kind, cell.delta_pct)


def test_zero_misroute_fleet_has_no_escalations(hetero_cells):
    for kind, cell in hetero_cells.items():
        f = cell.report["fleet"]
        assert f["completed"] == 8000
        assert f["escalations"] == 0


def test_semantic_beats_homogeneous_70b(hetero_cells):
    """The §5.1 lever measured: serving the short 89% of Azure traffic
    with an 8B model beats the homogeneous 70B fleet on tok/W."""
    homo = simulate_spec(
        TopologySpec.from_kind("homo", H100_LLAMA70B, LLAMA31_70B),
        AZURE, n_requests=8000, seed=0)
    sem = hetero_cells["semantic"]
    assert sem.sim_decode_tok_per_watt > 2.0 * homo.sim_decode_tok_per_watt


def test_misroute_sweep_monotone_and_never_double_counted():
    """Satellite acceptance: rising misroute rate monotonically degrades
    fleet tok/W (1% slack for integer re-sizing artifacts), escalations
    rise, every request still completes exactly once, and escalated
    requests' output is never double-counted — the fleet's lifetime decode
    token count equals the sum over completed requests of n_generated - 1
    (the first token of each serve comes out of prefill; every wasted
    pre-escalation token was backed out)."""
    rates = (0.0, 0.1, 0.2, 0.35)
    all_in, decode, esc = [], [], []
    for mr in rates:
        spec = TopologySpec.from_kind(
            "semantic_fleetopt", H100_LLAMA70B, LLAMA31_70B, b_short=4096,
            misroute_rate=mr, misroute_seed=0)
        cell = simulate_spec(spec, AZURE, n_requests=2500, seed=0)
        f = cell.report["fleet"]
        assert f["completed"] == 2500
        all_in.append(cell.sim_tok_per_watt)
        decode.append(cell.sim_decode_tok_per_watt)
        esc.append(f["escalations"] + f["migrations"])
    assert all(b <= a * 1.01 for a, b in zip(all_in, all_in[1:])), all_in
    assert all(b <= a * 1.01 for a, b in zip(decode, decode[1:])), decode
    assert all_in[-1] < all_in[0] * 0.95         # the degradation is real
    assert all(b >= a for a, b in zip(esc, esc[1:])) and esc[-1] > esc[0]


def test_escalated_tokens_conserved_end_to_end():
    policy, plan, registry = TopologySpec.from_kind(
        "semantic_fleetopt", H100_LLAMA70B, LLAMA31_70B, b_short=4096,
        misroute_rate=0.25, misroute_seed=0).build(AZURE)
    sim = FleetSim(policy, plan, registry=registry, rng_seed=0)
    from repro.serving import trace_requests
    reqs = trace_requests(AZURE, 1200, seed=0)
    rep = sim.run(reqs)
    assert rep["fleet"]["completed"] == 1200
    assert rep["fleet"]["escalations"] > 0
    metered = sum(grp.lifetime_tokens for grp in sim.groups.values())
    earned = sum(r.n_generated - 1 for grp in sim.groups.values()
                 for r in grp.completed)
    assert metered == earned
    # an escalated request finished exactly once, in the large pool
    escalated = [r for r in reqs if r.escalations]
    assert escalated
    assert all(r.pool.startswith("semantic-large") for r in escalated)
    assert all(r.finish_time >= 0 for r in escalated)
