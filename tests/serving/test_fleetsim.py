"""Fleet simulator: analytical-mode engine semantics + the tentpole
integration check — simulated FleetOpt >= 2x simulated Homo on Azure, and
simulated tok/W within tolerance of the analytical core.fleet prediction.

Everything is deterministic-seed; no jax touches the analytical engines.
"""
import math
import numpy as np
import pytest

from repro.core.modelspec import LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.core.topospec import TopologySpec
from repro.core.workloads import AGENT, AZURE
from repro.serving import (ContextRouter, EnergyMeter, FleetSim, PoolEngine,
                           PoolGroup, Request, RouterPolicy, simulate_spec,
                           trace_requests)

STREAMED = LLAMA31_70B.streamed_params


def _req(rid, plen, out, t=0.0):
    return Request(rid=rid, prompt=np.zeros(plen, np.int64),
                   max_new_tokens=out, arrival_time=t)


# --- analytical-mode engine unit behaviour ------------------------------

def test_analytical_engine_completes_and_meters():
    eng = PoolEngine(None, None, window=64, profile=H100_LLAMA70B,
                     n_slots=2, streamed_params=STREAMED)
    for i in range(5):
        eng.submit(_req(i, 8, 6))
    eng.run_until_drained(max_iters=500)
    assert len(eng.completed) == 5
    assert all(r.n_generated == 6 for r in eng.completed)
    # 5 requests x 5 metered decode tokens (the first token of each request
    # comes out of prefill and is not a decode-iteration token)
    assert eng.meter.tokens == 25
    assert eng.meter.joules > 0
    assert 0.0 < eng.occupancy <= 1.0


def test_analytical_engine_is_deterministic():
    def run():
        eng = PoolEngine(None, None, window=64, profile=H100_LLAMA70B,
                         n_slots=2, streamed_params=STREAMED, rng_seed=3)
        for i in range(6):
            eng.submit(_req(i, 7, 5))
        eng.run_until_drained(max_iters=500)
        return (eng.meter.joules, eng.meter.tokens,
                [r.finish_time for r in eng.completed])

    assert run() == run()


def test_chunked_prefill_delays_first_token():
    """With the chunked interleave a long prompt drains over several
    iterations, so TTFT grows with prompt length."""
    def ttft(plen):
        eng = PoolEngine(None, None, window=4096, profile=H100_LLAMA70B,
                         n_slots=1, streamed_params=STREAMED,
                         prefill_chunk=128)
        eng.submit(_req(0, plen, 3))
        eng.run_until_drained(max_iters=200)
        (r,) = eng.completed
        return r.first_token_time - r.arrival_time

    assert ttft(1024) > ttft(64) > 0


def test_arrival_gating_charges_idle_power():
    eng = PoolEngine(None, None, window=64, profile=H100_LLAMA70B,
                     n_slots=2, streamed_params=STREAMED,
                     respect_arrival=True)
    eng.submit(_req(0, 8, 4, t=1.0))      # arrives after 1s of idleness
    eng.run_until_drained(max_iters=100)
    assert len(eng.completed) == 1
    assert eng.meter.idle_joules == pytest.approx(
        H100_LLAMA70B.power_model.p_idle_w * 1.0, rel=1e-6)
    assert eng.completed[0].first_token_time >= 1.0


def test_overflow_eviction_backs_out_wasted_tokens():
    eng = PoolEngine(None, None, window=16, profile=H100_LLAMA70B,
                     n_slots=1, streamed_params=STREAMED,
                     evict_on_overflow=True)
    eng.submit(_req(0, 8, 500))           # can never fit window 16
    eng.run_until_drained(max_iters=100)
    assert len(eng.completed) == 0
    assert len(eng.overflowed) == 1
    (r,) = eng.overflowed
    assert r.preemptions == 1 and r.ready_time is not None
    # wasted decode work produces no counted output tokens (energy stays)
    assert eng.meter.tokens == 0
    assert eng.meter.joules > 0


# --- fleet-level integration (the tentpole acceptance) ------------------

@pytest.fixture(scope="module")
def azure_cells():
    return {kind: simulate_spec(
        TopologySpec.from_kind(kind, H100_LLAMA70B, LLAMA31_70B,
                               b_short=4096),
        AZURE, n_requests=8000, seed=0)
        for kind in ("homo", "fleetopt")}


def test_simulated_fleetopt_at_least_2x_homo_on_azure(azure_cells):
    homo = azure_cells["homo"].sim_decode_tok_per_watt
    fo = azure_cells["fleetopt"].sim_decode_tok_per_watt
    assert fo >= 2.0 * homo, (fo, homo)


def test_simulated_within_tolerance_of_analytical(azure_cells):
    """Stated tolerance: measured steady-state decode tok/W within 25% of
    the closed-form core.fleet sizing it was provisioned from (observed
    at seed 0 / 8k requests: homo -15%, fleetopt -2%)."""
    for kind, cell in azure_cells.items():
        assert abs(cell.delta_pct) < 25.0, (kind, cell.delta_pct)


def test_fleet_conservation_and_report_shape(azure_cells):
    for cell in azure_cells.values():
        f = cell.report["fleet"]
        assert f["completed"] == 8000
        assert f["tok_per_watt"] <= f["decode_tok_per_watt"]
        assert 0.0 <= f["prefill_energy_frac"] < 1.0
        assert f["ttft_p99_s"] >= f["ttft_p50_s"] > 0
        for role, s in cell.report.items():
            if role == "fleet":
                continue
            assert 0.0 <= s["occupancy"] <= 1.0


def test_overflow_migration_end_to_end():
    """A tight gamma forces short-pool overflows; migrated requests must
    re-prefill in the long pool and every request still completes."""
    spec = TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=8192, gamma=1.1)
    cell = simulate_spec(spec, AGENT, n_requests=1500, seed=1)
    f = cell.report["fleet"]
    assert f["migrations"] > 0
    assert f["completed"] == 1500
    # every migration is a short-pool preemption, finished in the long pool
    assert cell.report["short"]["preempted"] == f["migrations"]
    assert cell.report["long"]["completed"] >= f["migrations"]


def test_multipool_migration_chain_short_mid_long():
    """K = 3 ladder: a request whose actual total outgrows both the 2K and
    the 8K windows must migrate twice (pool-2K -> pool-8K -> pool-64K) and
    still complete in full."""
    policy, plan, _registry = TopologySpec.from_kind(
        "multipool", H100_LLAMA70B, LLAMA31_70B, gamma=2.0,
        windows=[2048, 8192, 65536]).build(AGENT)
    assert [p.name for p in sorted(plan.pools, key=lambda p: p.window)] \
        == ["pool-2K", "pool-8K", "pool-64K"]
    sim = FleetSim(policy, plan, model=LLAMA31_70B)
    # predicted total 900 + 100 = 1000 <= 2048/2 -> admitted to pool-2K;
    # actual total 8900 overflows the 2K window, then the 8K window
    chain = Request(rid=0, prompt=np.zeros(900, np.int64),
                    max_new_tokens=8000, arrival_time=0.0,
                    predicted_output=100)
    filler = [Request(rid=i, prompt=np.zeros(64, np.int64),
                      max_new_tokens=16, arrival_time=0.01 * i,
                      predicted_output=16) for i in range(1, 40)]
    rep = sim.run([chain] + filler)
    assert rep["fleet"]["completed"] == 40
    assert rep["fleet"]["migrations"] == 2      # hops, not unique requests
    assert chain.preemptions == 2
    assert chain.pool.startswith("pool-64K")    # finished in the top rung
    assert chain.n_generated == 8000


def test_multipool_end_to_end_on_trace():
    """A K = 3 plan runs a real trace through FleetSim: every request
    completes and each rung of the ladder serves traffic."""
    spec = TopologySpec.from_kind("multipool", H100_LLAMA70B, LLAMA31_70B,
                                  windows=[4096, 16384, 65536])
    cell = simulate_spec(spec, AZURE, n_requests=1000, seed=0)
    f = cell.report["fleet"]
    assert f["completed"] == 1000
    roles = [r for r in cell.report if r != "fleet"]
    assert roles == ["pool-4K", "pool-16K", "pool-64K"]
    assert all(cell.report[r]["completed"] > 0 for r in roles)
    assert f["tok_per_watt"] > 0


def test_pool_group_balances_by_total_assigned_work():
    """Regression pin for the intended PoolGroup semantics: replicas are
    balanced by cumulative *assigned* predicted work (routing happens
    before any engine runs, so there is no draining to track)."""
    from repro.serving import BatchedPoolEngine
    grp = PoolGroup("g", BatchedPoolEngine(
        instances=2, window=4096, profile=H100_LLAMA70B, n_slots=4,
        name="e", streamed_params=STREAMED))
    for i, total in enumerate((10, 10, 4, 30)):
        grp.submit(Request(rid=i, prompt=np.zeros(1, np.int64),
                           max_new_tokens=1, predicted_output=total - 1))
    # argmin of cumulative work: e0 <- r0 (10), e1 <- r1 (10),
    # e0 <- r2 (14), e1 <- r3 (40)
    assert grp.queue_rids(0) == [0, 2]
    assert grp.queue_rids(1) == [1, 3]
    assert list(grp._pending) == [14.0, 40.0]


def test_router_report_honors_measurement_window():
    """ContextRouter.report and the meters' steady-state window must agree:
    with an empty window the fleet roll-up reports nothing even though the
    lifetime totals are non-zero."""
    eng = PoolEngine(None, None, window=64, profile=H100_LLAMA70B,
                     n_slots=2, streamed_params=STREAMED)
    router = ContextRouter({"only": eng},
                           RouterPolicy(ladder=[("only", math.inf)]))
    eng.meter.measure_t1 = 0.0
    rep = router.run([_req(i, 8, 6) for i in range(3)])
    assert eng.meter.tokens > 0
    assert rep["fleet"]["tokens"] == 0
    assert rep["fleet"]["tok_per_watt"] == 0.0


def test_router_and_fleetsim_agree_on_measured_tokens():
    """The two report paths count the same steady-state window — they can
    no longer disagree on identical runs (the PR-1 defect)."""
    policy, plan, _registry = TopologySpec.from_kind(
        "fleetopt", H100_LLAMA70B, LLAMA31_70B, b_short=4096).build(AZURE)
    sim = FleetSim(policy, plan, model=LLAMA31_70B)
    rep = sim.run(trace_requests(AZURE, 600, seed=2))
    router_rep = sim.router.report()
    assert router_rep["fleet"]["tokens"] == rep["fleet"]["tokens"]
    # FleetSim additionally wall-clock-pads idle engines, so its joule
    # denominator can only be larger (both roll-ups sum raw meter values;
    # only the final display rounding differs)
    assert router_rep["fleet"]["joules"] <= rep["fleet"]["joules"] + 0.1


# --- prefill energy attribution (EnergyMeter.charge_prefill) ------------

def _prefill_time(n_tokens, mfu=0.8):
    prof = H100_LLAMA70B
    return (2.0 * STREAMED * n_tokens
            / (prof.tp * prof.chip.peak_bf16_flops * mfu))


def test_prefill_charged_at_compute_bound_power():
    m = EnergyMeter(H100_LLAMA70B)
    m.charge_prefill(1000, streamed_params=STREAMED)
    t = _prefill_time(1000)
    nom = H100_LLAMA70B.power_model.p_nom_w
    assert m.prefill_joules == pytest.approx(nom * t, rel=1e-9)
    # the old b = 1 decode operating point undercharged by ~2x
    assert m.prefill_joules > 1.5 * H100_LLAMA70B.power_w(1) * t


def test_fully_piggybacked_prefill_attributed_by_real_interval():
    """A chunk that fully hides behind decode has dt = 0, but its work
    happened over [sim_time - t, sim_time].  With sim_time just past the
    window end, the old code midpoint-tested the zero-length dt at
    sim_time and attributed *nothing*; pro-rating credits the in-window
    share of the real interval."""
    m = EnergyMeter(H100_LLAMA70B)
    t = _prefill_time(100)
    m.sim_time_s = 5.0
    m.measure_t0, m.measure_t1 = 0.0, 5.0 - t / 2.0  # half interval inside
    dt = m.charge_prefill(100, streamed_params=STREAMED, overlap_s=1e9)
    assert dt == 0.0
    assert m.prefill_joules > 0
    assert m.m_prefill_joules == pytest.approx(0.5 * m.prefill_joules,
                                               rel=1e-9)


def test_boundary_straddling_prefill_prorated():
    """A charge interval straddling the window boundary is attributed by
    exact overlap, like charge_idle — not all-or-nothing."""
    m = EnergyMeter(H100_LLAMA70B)
    t = _prefill_time(4096)
    m.measure_t0, m.measure_t1 = 0.0, t / 2.0   # half the interval inside
    m.charge_prefill(4096, streamed_params=STREAMED)
    assert m.m_prefill_joules == pytest.approx(0.5 * m.prefill_joules,
                                               rel=1e-9)


def test_trace_requests_clips_and_predicts():
    reqs = trace_requests(AZURE, 200, seed=0, max_total=4096)
    assert len(reqs) == 200
    assert all(r.prompt_len + r.max_new_tokens <= 4096 for r in reqs)
    assert all(r.predicted_output == int(round(AZURE.mean_output))
               for r in reqs)
    # Poisson arrivals are strictly increasing
    ts = [r.arrival_time for r in reqs]
    assert all(b > a for a, b in zip(ts, ts[1:]))
