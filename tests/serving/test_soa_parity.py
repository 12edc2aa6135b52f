"""SoA parity: the batched pool engine (serving.soa) must reproduce the
scalar per-instance `PoolEngine` semantics **bit-for-bit** — admission
order, chunked-prefill interleave, window-ceiling eviction, escalation
detection/backout conservation, prefill-phase FIFO, and every meter
counter.  The golden tests pin the seed semantics on crafted streams; the
hypothesis property test (self-skipping) fuzzes random request streams.

The Table A-D golden anchors ride in CI separately: the committed
`benchmarks/results/fleet_sim.json` baseline was generated by the
per-instance engines and `perf_diff.py --fleet` diffs every tok/W cell of
the batched bench against it (observed: every cell bit-identical).
"""
import copy

import numpy as np
import pytest

from repro.core.modelspec import LLAMA31_70B
from repro.core.profiles import H100_LLAMA70B
from repro.core.workloads import AZURE
from repro.serving import BatchedPoolEngine, PoolEngine, Request

STREAMED = LLAMA31_70B.streamed_params


def _req(rid, plen, out, t=0.0, pred=None, esc=None, pdone=False):
    r = Request(rid=rid, prompt=np.broadcast_to(np.int64(0), (plen,)),
                max_new_tokens=out, arrival_time=t, predicted_output=pred)
    r.escalate_at = esc
    r.prefill_done = pdone
    return r


def _run_both(reqs_by_inst, **kw):
    """Run the same per-instance streams through N scalar engines and one
    batched engine (identical construction: seed stride 7919, shared base
    seed) and return both."""
    n = len(reqs_by_inst)
    scalars = [PoolEngine(None, None, profile=H100_LLAMA70B,
                          streamed_params=STREAMED, rng_seed=11 + 7919 * j,
                          name=f"p#{j}", respect_arrival=True, **kw)
               for j in range(n)]
    batched = BatchedPoolEngine(instances=n, profile=H100_LLAMA70B,
                                streamed_params=STREAMED, rng_seed=11,
                                name="p", respect_arrival=True, **kw)
    for j, reqs in enumerate(reqs_by_inst):
        for r in reqs:
            scalars[j].submit(copy.copy(r))
        for r in reqs:
            batched.submit(copy.copy(r), j)
    for e in scalars:
        e.run_until_drained(max_iters=200_000)
    batched.run_until_drained(max_iters=200_000)
    return scalars, batched


def _assert_bit_equal(scalars, batched):
    for j, e in enumerate(scalars):
        b = batched.bank
        assert e.meter.joules == b.joules[j]
        assert e.meter.m_joules == b.m_joules[j]
        assert e.meter.prefill_joules == b.prefill_joules[j]
        assert e.meter.m_prefill_joules == b.m_prefill_joules[j]
        assert e.meter.idle_joules == b.idle_joules[j]
        assert e.meter.m_idle_joules == b.m_idle_joules[j]
        assert e.meter.tokens == b.tokens[j]
        assert e.meter.m_tokens == b.m_tokens[j]
        assert e.meter.prefill_tokens == b.prefill_tokens[j]
        assert e.meter.sim_time_s == b.sim_time_s[j]
        assert e.slot_seconds == batched.slot_seconds[j]
        assert e.preempted == batched.preempted[j]
        assert e.n_escalated == batched.n_escalated[j]
        for field in ("completed", "overflowed", "escalated", "relayed"):
            sa = getattr(e, field)
            sb = getattr(batched, field)[j]
            assert [r.rid for r in sa] == [r.rid for r in sb], field
            for ra, rb in zip(sa, sb):
                assert (ra.finish_time, ra.first_token_time,
                        ra.n_generated, ra.preemptions, ra.escalations,
                        ra.ready_time) == \
                       (rb.finish_time, rb.first_token_time,
                        rb.n_generated, rb.preemptions, rb.escalations,
                        rb.ready_time), (field, ra.rid)


def test_parity_admission_order_and_chunked_interleave():
    """Head-gated FIFO admission into the lowest free slot, chunked
    prefill riding decode iterations — the core serving semantics."""
    rng = np.random.default_rng(3)
    reqs = [[_req(i + 100 * j, int(rng.integers(1, 3000)),
                  int(rng.integers(1, 150)), t=0.04 * i)
             for i in range(40)] for j in range(3)]
    _assert_bit_equal(*_run_both(reqs, window=4096, n_slots=4,
                                 prefill_chunk=256))


def test_parity_window_ceiling_overflow_chain():
    """evict_on_overflow: requests outgrowing the window are backed out
    (tokens clawed back, energy kept) and leave via the overflow outbox
    in eviction order."""
    reqs = [[_req(j * 50, 100, 5000)] +
            [_req(j * 50 + 1 + i, 40, 30, t=0.01 * i) for i in range(12)]
            for j in range(2)]
    scalars, batched = _run_both(reqs, window=256, n_slots=2,
                                 prefill_chunk=128, evict_on_overflow=True)
    _assert_bit_equal(scalars, batched)
    assert all(len(o) > 0 for o in batched.overflowed)


def test_parity_escalation_backout_conservation():
    """Misroute detection at escalate_at: wasted decode tokens are backed
    out of the meters so escalated output is never double-counted."""
    reqs = [[_req(i, 64, 400, esc=6) for i in range(5)] for _ in range(2)]
    scalars, batched = _run_both(reqs, window=8192, n_slots=2,
                                 prefill_chunk=128)
    _assert_bit_equal(scalars, batched)
    assert int(batched.n_escalated.sum()) == 10
    # conservation: every metered token belongs to a completed request
    assert int(batched.bank.tokens.sum()) == sum(
        r.n_generated - 1 for lst in batched.completed for r in lst)


def test_parity_prefill_phase_fifo():
    """Dedicated prefill pools drain oldest-first across slot recycling
    and hand requests off with first token + ready_time set."""
    rng = np.random.default_rng(9)
    reqs = [[_req(i + 30 * j, int(rng.integers(64, 7000)), 1, t=0.03 * i)
             for i in range(25)] for j in range(2)]
    scalars, batched = _run_both(reqs, window=8192, n_slots=4,
                                 prefill_chunk=512, phase="prefill")
    _assert_bit_equal(scalars, batched)
    assert all(len(h) > 0 for h in batched.handoff)


def test_parity_prefilled_admission_and_unchunked():
    """disagg decode admission (prefill_done=True: no prefill charge) and
    the legacy unchunked immediate-prefill path."""
    pdone = [[_req(i, 128, 20, t=0.01 * i, pdone=True) for i in range(8)]
             for _ in range(2)]
    for r_list in pdone:
        for r in r_list:
            r.ready_time = r.arrival_time
            r.generated = [7]
    _assert_bit_equal(*_run_both(pdone, window=4096, n_slots=2,
                                 prefill_chunk=256))
    plain = [[_req(i, 64, 25, t=0.02 * i) for i in range(10)]
             for _ in range(2)]
    _assert_bit_equal(*_run_both(plain, window=4096, n_slots=3,
                                 prefill_chunk=0))


def test_batched_fleet_matches_scalar_seed_numbers():
    """End-to-end anchor: the batched FleetSim reproduces the seed
    per-instance fleet numbers recorded before the SoA refactor (Azure
    fleetopt, 1000 requests, seed 0 — the committed --quick baseline
    cell, regenerated by the per-engine implementation)."""
    from repro.core.topospec import TopologySpec
    from repro.serving import simulate_spec
    spec = TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=4096)
    cell = simulate_spec(spec, AZURE, n_requests=1000, seed=0)
    f = cell.report["fleet"]
    assert f["completed"] == 1000
    assert round(cell.sim_decode_tok_per_watt, 2) == 5.66
    assert round(cell.sim_tok_per_watt, 2) == 1.81


# --- property test: random streams, scalar vs batched --------------------

try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:                                    # pragma: no cover
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    request_lists = st.lists(
        st.tuples(st.integers(1, 2000),     # prompt len
                  st.integers(1, 120),      # output len
                  st.floats(0.0, 2.0),      # inter-arrival gap
                  st.sampled_from([None, None, 4, 16])),  # escalate_at
        min_size=1, max_size=25)

    @settings(max_examples=25, deadline=None)
    @given(streams=st.lists(request_lists, min_size=1, max_size=3),
           n_slots=st.integers(1, 4),
           chunk=st.sampled_from([0, 64, 256]),
           window=st.sampled_from([512, 4096]),
           evict=st.booleans())
    def test_property_scalar_and_batched_step_identically(
            streams, n_slots, chunk, window, evict):
        rid = 0
        reqs_by_inst = []
        for stream in streams:
            t = 0.0
            reqs = []
            for plen, out, gap, esc in stream:
                t += gap
                reqs.append(_req(rid, plen, out, t=t, esc=esc))
                rid += 1
            reqs_by_inst.append(reqs)
        scalars, batched = _run_both(reqs_by_inst, window=window,
                                     n_slots=n_slots, prefill_chunk=chunk,
                                     evict_on_overflow=evict)
        _assert_bit_equal(scalars, batched)
else:                                                  # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev)")
    def test_property_scalar_and_batched_step_identically():
        pass
