"""Disaggregated prefill/decode serving (core.disagg through FleetSim):
prefill-phase engine semantics, the KV-handoff hop with its interconnect
delay + energy, the disagg_fleetopt overflow re-prefill chain, and the
tentpole integration check — measured disagg decode tok/W within 25% of
the analytical decode-fleet sizing, with handoff energy nonzero and
accounted.  Deterministic seeds; no jax."""
import numpy as np
import pytest

from repro.core.disagg import HANDOFF_J_PER_BYTE
from repro.core.modelspec import LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.core.topospec import TopologySpec
from repro.core.workloads import AZURE
from repro.serving import (EnergyMeter, FleetSim, PoolEngine, Request,
                           simulate_spec)

STREAMED = LLAMA31_70B.streamed_params


def _req(rid, plen, out, t=0.0, pred=None):
    return Request(rid=rid, prompt=np.zeros(plen, np.int64),
                   max_new_tokens=out, arrival_time=t,
                   predicted_output=pred)


# --- prefill-phase engine unit behaviour --------------------------------

def test_prefill_phase_engine_hands_off_without_decoding():
    eng = PoolEngine(None, None, window=4096, profile=H100_LLAMA70B,
                     n_slots=2, streamed_params=STREAMED,
                     phase="prefill", prefill_chunk=128)
    for i in range(3):
        eng.submit(_req(i, 256, 5))
    eng.run_until_drained(max_iters=500)
    assert len(eng.completed) == 0          # prefill pools finish nothing
    assert len(eng.handoff) == 3 and len(eng.relayed) == 3
    for r in eng.handoff:
        assert r.prefill_done
        assert r.n_generated == 1 and len(r.generated) == 1
        assert r.first_token_time > 0       # TTFT set at prefill drain
        assert r.ready_time == r.first_token_time
    # no decode iterations ever ran: all energy is prefill compute
    assert eng.meter.tokens == 0
    assert eng.meter.prefill_tokens == 3 * 256
    assert eng.meter.prefill_joules == pytest.approx(eng.meter.joules)


def test_prefill_phase_is_fifo_across_slot_recycling():
    """A giant prompt admitted into a freed low-index slot must not starve
    an older, nearly-drained prompt in a higher slot."""
    eng = PoolEngine(None, None, window=8192, profile=H100_LLAMA70B,
                     n_slots=2, streamed_params=STREAMED,
                     phase="prefill", prefill_chunk=128,
                     respect_arrival=True)
    eng.submit(_req(0, 64, 1, t=0.0))       # slot 0, drains fast
    eng.submit(_req(1, 4096, 1, t=0.0))     # slot 1, long
    eng.submit(_req(2, 4096, 1, t=0.001))   # recycles slot 0
    eng.run_until_drained(max_iters=2000)
    done = {r.rid: r.first_token_time for r in eng.relayed}
    assert done[0] < done[1] < done[2]      # oldest-first, not slot-index


def test_prefill_phase_defaults_unchunked_zero_to_a_real_chunk():
    """prefill_chunk=0 means 'unchunked' for decode engines; a prefill-
    phase engine must not take it literally (a 0 budget would spin
    without ever draining a prompt)."""
    eng = PoolEngine(None, None, window=4096, profile=H100_LLAMA70B,
                     n_slots=1, streamed_params=STREAMED,
                     phase="prefill", prefill_chunk=0)
    assert eng.prefill_chunk == 512
    eng.submit(_req(0, 64, 1))
    eng.run_until_drained(max_iters=50)
    assert len(eng.relayed) == 1


def test_prefill_phase_rejects_model_mode():
    with pytest.raises(ValueError):
        PoolEngine(object(), object(), window=64, profile=H100_LLAMA70B,
                   phase="prefill")
    with pytest.raises(ValueError):
        PoolEngine(None, None, window=64, profile=H100_LLAMA70B,
                   streamed_params=STREAMED, phase="nope")


def test_prefilled_admission_skips_prefill_charge():
    """A decode pool admitting a handed-off request must not re-run or
    re-charge prefill, must preserve the upstream TTFT, and decodes the
    remaining max_new - 1 tokens."""
    eng = PoolEngine(None, None, window=4096, profile=H100_LLAMA70B,
                     n_slots=2, streamed_params=STREAMED)
    req = _req(0, 100, 6)
    req.prefill_done = True
    req.generated = [7]
    req.n_generated = 1
    req.first_token_time = 0.123
    eng.submit(req)
    eng.run_until_drained(max_iters=100)
    assert len(eng.completed) == 1
    assert req.n_generated == 6
    assert req.first_token_time == 0.123    # set by the prefill pool
    assert eng.meter.prefill_joules == 0.0
    assert eng.meter.prefill_tokens == 0
    assert eng.meter.tokens == 5            # tokens 2..6 are decode steps


# --- KV-handoff energy metering -----------------------------------------

def test_charge_handoff_prorates_measurement_window():
    m = EnergyMeter(H100_LLAMA70B)
    e = m.charge_handoff(1e9, start_s=0.0, duration_s=1.0,
                         j_per_byte=HANDOFF_J_PER_BYTE)
    assert e == pytest.approx(1e9 * HANDOFF_J_PER_BYTE)
    assert m.handoff_joules == pytest.approx(e)
    assert m.m_handoff_joules == pytest.approx(e)   # (0, inf) window
    assert m.sim_time_s == 0.0     # transfers never advance the clock
    # half the transfer interval outside the window -> half attributed
    m2 = EnergyMeter(H100_LLAMA70B)
    m2.measure_t0, m2.measure_t1 = 0.0, 0.5
    m2.charge_handoff(1e9, start_s=0.0, duration_s=1.0,
                      j_per_byte=HANDOFF_J_PER_BYTE)
    assert m2.m_handoff_joules == pytest.approx(0.5 * e)
    assert m2.handoff_joules == pytest.approx(e)    # totals keep it all


# --- router / topology wiring -------------------------------------------

def test_disagg_topology_routes_into_prefill_pools():
    policy, plan, _registry = TopologySpec.from_kind(
        "disagg_fleetopt", H100_LLAMA70B, LLAMA31_70B, b_short=4096,
        gamma=2.0).build(AZURE)
    roles = [p.name for p in sorted(plan.pools, key=lambda p: p.window)]
    assert roles == ["prefill-8K", "decode-8K", "prefill-64K", "decode-64K"]
    assert policy.ladder == [("prefill-8K", 8192.0), ("prefill-64K", float("inf"))]
    sim = FleetSim(policy, plan, model=LLAMA31_70B)
    assert sim.handoff_to == {"prefill-8K": "decode-8K",
                              "prefill-64K": "decode-64K"}
    assert sim.overflow_to == {"decode-8K": "prefill-64K"}
    assert sim.router.route(_req(0, 100, 10, pred=10)) == "prefill-8K"
    assert sim.router.route(_req(1, 9000, 10, pred=10)) == "prefill-64K"


def test_disagg_overflow_reprefills_in_long_slice():
    """disagg_fleetopt overflow chain: a mispredicted request evicted from
    decode-8K re-prefills in prefill-64K (its KV was dropped) and finishes
    in decode-64K — two KV handoffs, one migration."""
    policy, plan, _registry = TopologySpec.from_kind(
        "disagg_fleetopt", H100_LLAMA70B, LLAMA31_70B, b_short=4096,
        gamma=2.0).build(AZURE)
    sim = FleetSim(policy, plan, model=LLAMA31_70B)
    chain = _req(0, 900, 8000, pred=100)    # predicted 1000 -> short slice
    rep = sim.run([chain])
    assert rep["fleet"]["completed"] == 1
    assert rep["fleet"]["migrations"] == 1
    assert rep["fleet"]["handoffs"] == 2    # original + post-evict re-entry
    assert chain.preemptions == 1
    assert chain.pool.startswith("decode-64K")
    assert chain.prefill_role == "prefill-64K"
    assert chain.n_generated == 8000


# --- fleet-level integration (the tentpole acceptance) ------------------

@pytest.fixture(scope="module")
def disagg_cells():
    return {kind: simulate_spec(
        TopologySpec.from_kind(kind, H100_LLAMA70B, LLAMA31_70B,
                               b_short=4096),
        AZURE, n_requests=8000, seed=0)
        for kind in ("disagg", "disagg_fleetopt")}


def test_disagg_measured_within_tolerance_of_analytical(disagg_cells):
    """Stated tolerance: measured steady-state decode tok/W within 25% of
    the closed-form decode-fleet sizing (observed at seed 0 / 8k requests:
    disagg -17%, disagg_fleetopt -15%)."""
    for kind, cell in disagg_cells.items():
        assert abs(cell.delta_pct) < 25.0, (kind, cell.delta_pct)
        # the whole-fleet analytical number additionally pays the
        # dedicated prefill pools, so it sits strictly below decode-only
        assert cell.analytical_fleet_tok_per_watt \
            < cell.analytical_tok_per_watt


def test_disagg_handoff_energy_nonzero_and_accounted(disagg_cells):
    for kind, cell in disagg_cells.items():
        f = cell.report["fleet"]
        assert f["handoffs"] >= f["completed"] == 8000
        assert f["kv_handoff_joules"] > 0
        assert f["kv_handoff_gb"] > 0
        assert 0 < f["kv_handoff_energy_frac"] < 0.05   # real but small
        # windowed attribution can only be a share of the per-byte total
        total_j = f["kv_handoff_gb"] * 1e9 * HANDOFF_J_PER_BYTE
        assert f["kv_handoff_joules"] <= total_j * (1 + 1e-6)


def test_disagg_removes_prefill_interference_from_decode_pools(disagg_cells):
    """The measured finding the topology exists for: decode pools in a
    disaggregated fleet meter zero prefill energy (it all lives in the
    prefill pools), and every decode-pool TTFT is inherited from a
    prefill pool."""
    for kind, cell in disagg_cells.items():
        for role, s in cell.report.items():
            if role == "fleet":
                continue
            if s["phase"] == "decode":
                assert s["completed"] > 0 and s["relayed"] == 0
                assert s["m_prefill_joules"] == 0.0, (role, s)
            else:
                assert s["completed"] == 0 and s["relayed"] > 0
                assert s["m_prefill_joules"] > 0.0, (role, s)


def test_prefill_role_latency_includes_downstream_metrics(disagg_cells):
    """latency_by_role for a prefill-phase pool covers the requests it
    relayed — including the e2e/TPOT metrics their downstream decode
    pool filled in after the prefill pool drained (regression pin: the
    SoA summaries are snapshotted per pool at drain time, and prefill
    pools' percentiles must be refreshed once the fleet finishes)."""
    from repro.serving import trace_requests
    policy, plan, reg = TopologySpec.from_kind(
        "disagg", H100_LLAMA70B, LLAMA31_70B, b_short=4096).build(AZURE)
    sim = FleetSim(policy, plan, registry=reg)
    sim.run(trace_requests(AZURE, 400, seed=2))
    for role, lat in sim.latency_by_role().items():
        assert {"ttft_p99_s", "e2e_p99_s", "tpot_p99_ms"} <= set(lat), \
            (role, lat)


def test_disagg_ttft_under_unconstrained_sizing(disagg_cells):
    """Dedicated prefill removes the interleave competition: plain disagg
    meets the 500 ms TTFT p99 already at the unconstrained Eq. 4 sizing
    (fleetopt at the same sizing violates it by ~3x — Table A)."""
    f = disagg_cells["disagg"].report["fleet"]
    assert f["ttft_p99_s"] <= 0.5, f["ttft_p99_s"]
