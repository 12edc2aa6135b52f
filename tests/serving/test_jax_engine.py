"""JAX-engine parity: the jit/vmap drain loop (serving.jax_engine) must
reproduce the numpy `BatchedPoolEngine` oracle — admission order, chunked
prefill interleave, window-ceiling eviction, escalation backout, the
prefill-phase FIFO, every meter counter, and the per-request event record
(finish/first-token times, preemption counts, outbox order).

The contract is float-parity, not bit-parity: masked-lane arithmetic adds
exactly +0.0 so almost every path is bit-identical, but multi-slot chunk
spills accumulate in a different association order on device
(ulp-level).  The acceptance gate is rtol=1e-9 on meters and exact
equality on every integer/ordering field; the numpy engine keeps its
bit-exact parity contract against the scalar engines untouched
(tests/serving/test_soa_parity.py).
"""
import copy
import re
from functools import partial

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.hardware import H100
from repro.core.modelspec import LLAMA31_70B, ModelSpec
from repro.core.moe import moe_profile
from repro.core.power import H100_POWER
from repro.core.profiles import B200_LLAMA70B, H100_LLAMA70B
from repro.core.workloads import AZURE
from repro.models.compat import enable_x64
from repro.serving import BatchedPoolEngine, Request, jax_engine
from repro.serving.engine import _NEVER
from repro.serving.jax_engine import JaxPoolEngine, drain_engines

STREAMED = LLAMA31_70B.streamed_params
# a hybrid Mamba-2 binding (Nemotron-3-Super's shape): each sequence's
# recurrent state adds S ms to every decode step it is in
HYBRID = moe_profile(ModelSpec(
    "hybrid", n_params=120e9, n_layers=88, n_kv_heads=2, head_dim=128,
    n_active_params=12e9, attn_layer_fraction=8 / 88, n_state_layers=40,
    state_bytes_per_layer=4_255_744.0), H100, H100_POWER, tp=8)


def _req(rid, plen, out, t=0.0, pred=None, esc=None, pdone=False):
    r = Request(rid=rid, prompt=np.broadcast_to(np.int64(0), (plen,)),
                max_new_tokens=out, arrival_time=t, predicted_output=pred)
    r.escalate_at = esc
    r.prefill_done = pdone
    if pdone:
        r.ready_time = t
        r.generated = [7]
    return r


def _mk(cls, reqs_by_inst, *, profile=H100_LLAMA70B, measure=None, **kw):
    eng = cls(instances=len(reqs_by_inst), profile=profile,
              streamed_params=STREAMED, rng_seed=11, name="p",
              respect_arrival=True, **kw)
    if measure is not None:               # (t0, t1) measurement window
        eng.bank.measure_t0, eng.bank.measure_t1 = measure
    for j, reqs in enumerate(reqs_by_inst):
        for r in reqs:
            eng.submit(copy.copy(r), j)
    eng.sort_queues()
    return eng


def _run_both(reqs_by_inst, **kw):
    """The same per-instance streams through the numpy oracle and the JAX
    engine (identical construction)."""
    ref = _mk(BatchedPoolEngine, reqs_by_inst, **kw)
    jx = _mk(JaxPoolEngine, reqs_by_inst, **kw)
    ref.run_until_drained(max_iters=200_000)
    jx.run_until_drained(max_iters=200_000)
    return ref, jx


def _assert_parity(ref, jx, rtol=1e-9):
    b, c = ref.bank, jx.bank
    for k in ("joules", "m_joules", "prefill_joules", "m_prefill_joules",
              "idle_joules", "m_idle_joules", "dispatch_joules",
              "m_dispatch_joules", "sim_time_s"):
        np.testing.assert_allclose(getattr(c, k), getattr(b, k),
                                   rtol=rtol, atol=1e-12, err_msg=k)
    for k in ("tokens", "m_tokens", "prefill_tokens"):
        np.testing.assert_array_equal(getattr(c, k), getattr(b, k),
                                      err_msg=k)
    np.testing.assert_allclose(jx.slot_seconds, ref.slot_seconds,
                               rtol=rtol, atol=1e-12)
    np.testing.assert_allclose(jx.m_slot_seconds, ref.m_slot_seconds,
                               rtol=rtol, atol=1e-12)
    np.testing.assert_array_equal(jx.preempted, ref.preempted)
    np.testing.assert_array_equal(jx.n_escalated, ref.n_escalated)
    for field in ("completed", "overflowed", "escalated", "relayed",
                  "handoff"):
        for j in range(ref.instances):
            sa = getattr(ref, field)[j]
            sb = getattr(jx, field)[j]
            assert [r.rid for r in sa] == [r.rid for r in sb], (field, j)
            for ra, rb in zip(sa, sb):
                assert ra.n_generated == rb.n_generated, (field, ra.rid)
                assert ra.preemptions == rb.preemptions, (field, ra.rid)
                assert ra.escalations == rb.escalations, (field, ra.rid)
                assert ra.prefill_done == rb.prefill_done, (field, ra.rid)
                assert (ra.generated is None) == (rb.generated is None)
                if ra.generated is not None:
                    assert ra.generated == rb.generated, (field, ra.rid)
                for tk in ("finish_time", "first_token_time"):
                    ta, tb = getattr(ra, tk), getattr(rb, tk)
                    assert ta == pytest.approx(tb, rel=rtol, abs=1e-12), \
                        (field, ra.rid, tk)
                if ra.ready_time is None:
                    assert rb.ready_time is None, (field, ra.rid)
                else:
                    assert ra.ready_time == pytest.approx(
                        rb.ready_time, rel=rtol, abs=1e-12), (field, ra.rid)


# parity scenarios: each is a list of (per-instance streams, engine kw),
# one entry per engine of one `drain_engines` call

def _case_interleave():
    rng = np.random.default_rng(3)
    reqs = [[_req(i + 100 * j, int(rng.integers(1, 3000)),
                  int(rng.integers(1, 150)), t=0.04 * i)
             for i in range(40)] for j in range(3)]
    return [(reqs, dict(window=4096, n_slots=4, prefill_chunk=256))]


def _case_overflow_chain():
    reqs = [[_req(j * 50, 100, 5000)] +
            [_req(j * 50 + 1 + i, 40, 30, t=0.01 * i) for i in range(12)]
            for j in range(2)]
    return [(reqs, dict(window=256, n_slots=2, prefill_chunk=128,
                        evict_on_overflow=True))]


def _case_escalation_in_window():
    # the measurement window opens mid-run
    reqs = [[_req(i, 64, 400, esc=6, t=0.05 * i) for i in range(5)]
            for _ in range(2)]
    return [(reqs, dict(window=8192, n_slots=2, prefill_chunk=128,
                        measure=(0.1, 1e9)))]


def _case_prefill_fifo():
    rng = np.random.default_rng(9)
    reqs = [[_req(i + 30 * j, int(rng.integers(64, 7000)), 1, t=0.03 * i)
             for i in range(25)] for j in range(2)]
    return [(reqs, dict(window=8192, n_slots=4, prefill_chunk=512,
                        phase="prefill"))]


def _case_ragged():
    """Engines with different instance counts, slot counts, queue
    lengths, profiles and phases."""
    rng = np.random.default_rng(17)

    def mkstreams(n_inst, n, stride):
        return [[_req(1000 * stride + i + 100 * j,
                      int(rng.integers(1, 2000)),
                      int(rng.integers(1, 80)), t=0.05 * i)
                 for i in range(n)] for j in range(n_inst)]

    return [(mkstreams(1, 30, 0),
             dict(window=4096, n_slots=4, prefill_chunk=256)),
            (mkstreams(3, 7, 1),
             dict(window=2048, n_slots=2, prefill_chunk=128,
                  evict_on_overflow=True, profile=B200_LLAMA70B)),
            (mkstreams(2, 18, 2),
             dict(window=8192, n_slots=3, prefill_chunk=512,
                  phase="prefill"))]


def _case_hybrid():
    """A stateful profile over 128 slots: long decodes fill the slot axis
    and coast between completions while the rest of the queue waits."""
    rng = np.random.default_rng(23)
    reqs = [[_req(i + 1000 * j, int(rng.integers(16, 600)),
                  int(rng.integers(200, 1500)), t=0.002 * i)
             for i in range(160)] for j in range(2)]
    return [(reqs, dict(window=8192, n_slots=128, prefill_chunk=512,
                        profile=HYBRID))]


def _case_wide_spill():
    """A wide pool fed bursts of short prompts, in each phase: every
    512-token chunk spills over three to five slots in one step and ends
    mid-slot, and in the decode phase its first charge hides behind the
    decode tau of the slots already generating; the measurement window
    closes mid-run."""
    rng = np.random.default_rng(29)

    def streams(base, out_hi):
        return [[_req(base + i + 1000 * j, int(rng.integers(90, 170)),
                      int(rng.integers(1, out_hi + 1)), t=0.05 * (i // 32))
                 for i in range(96)] for j in range(2)]

    return [(streams(0, 200), dict(window=8192, n_slots=64,
                                   prefill_chunk=512, measure=(0.02, 0.3))),
            (streams(5000, 1), dict(window=8192, n_slots=64,
                                    prefill_chunk=512, phase="prefill"))]


def _case_burst():
    """More requests arrive at once than the admission window holds: 300
    slots (S pads to 512, Q to 1024, so K = 128) filled from a standing
    queue, whole in the decode phase, where prefilled requests all finish
    together, and in the prefill phase as prompts of mixed length drain."""
    rng = np.random.default_rng(31)
    decode = [[_req(i + 1000 * j, 64, 20, pdone=True) for i in range(n)]
              for j, n in enumerate((600, 450))]
    prefill = [[_req(5000 + i, int(rng.integers(16, 200)), 1)
                for i in range(700)]]
    return [(decode, dict(window=4096, n_slots=300, prefill_chunk=512)),
            (prefill, dict(window=4096, n_slots=300, prefill_chunk=512,
                           phase="prefill"))]


CASES = {"interleave": _case_interleave,
         "overflow_chain": _case_overflow_chain,
         "escalation_in_window": _case_escalation_in_window,
         "prefill_fifo": _case_prefill_fifo,
         "ragged": _case_ragged,
         "hybrid": _case_hybrid,
         "wide_spill": _case_wide_spill,
         "burst": _case_burst}


def test_jax_parity_admission_and_chunked_interleave():
    (reqs, kw), = _case_interleave()
    _assert_parity(*_run_both(reqs, **kw))


def test_jax_parity_window_ceiling_overflow_chain():
    (reqs, kw), = _case_overflow_chain()
    ref, jx = _run_both(reqs, **kw)
    _assert_parity(ref, jx)
    assert all(len(o) > 0 for o in jx.overflowed)


def test_jax_parity_escalation_backout_in_window():
    """Escalations *inside* the measurement window: the windowed m_*
    counters must back out exactly what the numpy oracle backs out."""
    (reqs, kw), = _case_escalation_in_window()
    ref, jx = _run_both(reqs, **kw)
    _assert_parity(ref, jx)
    assert int(jx.n_escalated.sum()) == 10


def test_jax_parity_prefill_phase_fifo():
    (reqs, kw), = _case_prefill_fifo()
    ref, jx = _run_both(reqs, **kw)
    _assert_parity(ref, jx)
    assert all(len(h) > 0 for h in jx.handoff)
    # handoff first tokens are live LCG values, not placeholders
    for j in range(jx.instances):
        for ra, rb in zip(ref.handoff[j], jx.handoff[j]):
            assert ra.generated == rb.generated


def test_jax_parity_prefilled_admission_and_dispatch():
    """disagg decode admission (prefill_done: no prefill charge) plus a
    per-step MoE dispatch floor."""
    pdone = [[_req(i, 128, 20, t=0.01 * i, pdone=True) for i in range(8)]
             for _ in range(2)]
    _assert_parity(*_run_both(pdone, window=4096, n_slots=2,
                              prefill_chunk=256, dispatch_ms=2.0))


def test_jax_unchunked_decode_unsupported():
    """The unchunked immediate-prefill admission path advances the clock
    mid-admission — explicitly out of the JAX engine's contract."""
    with pytest.raises(NotImplementedError):
        JaxPoolEngine(instances=1, window=4096, profile=H100_LLAMA70B,
                      streamed_params=STREAMED, prefill_chunk=0)


def _drain_case(specs):
    """The numpy oracle's engines, each drained alone, and the compiled
    engines of one `drain_engines` call over the same specs, with each
    compiled engine's staged outputs (every out array, meter row and
    `it`) before it is finalized."""
    refs = [_mk(BatchedPoolEngine, s, **kw) for s, kw in specs]
    jxs = [_mk(JaxPoolEngine, s, **kw) for s, kw in specs]
    for e in refs:
        e.run_until_drained(max_iters=200_000)
    drain_engines(jxs, max_iters=200_000)
    staged = [dict(e._staged) for e in jxs]
    for e in jxs:
        e.run_until_drained(max_iters=200_000)   # consumes staged result
    return refs, jxs, staged


def test_jax_parity_hybrid_state_step_coasts():
    """S enters the compiled step and the coast's closed form as it enters
    the numpy oracle's roofline: a stateful drain that coasts matches."""
    (reqs, kw), = _case_hybrid()
    refs, jxs, staged = _drain_case([(reqs, kw)])
    assert HYBRID.roofline.s_ms > 0 and jxs[0].n_slots >= 128
    _assert_parity(refs[0], jxs[0])
    # fewer loop iterations than the longest request has decode steps
    longest = max(r.max_new_tokens for q in reqs for r in q)
    assert 0 < int(staged[0]["it"]) < longest
    assert int(jxs[0].bank.tokens.sum()) > 100 * longest


def test_drain_engines_ragged_batch():
    """One `drain_engines` call over engines with different instance
    counts, slot counts, queue lengths, profiles and phases must equal
    each engine drained alone by the numpy oracle — the padding masks may
    not leak work into (or out of) dead rows."""
    refs, jxs, _ = _drain_case(_case_ragged())
    for ref, jx in zip(refs, jxs):
        _assert_parity(ref, jx)


@pytest.mark.parametrize("case", sorted(CASES))
def test_select_lowering_drains_bit_identically(case, monkeypatch):
    """The TPU's lowering of the drain's row lookups (a select over the
    indexed axis), compiled here on the CPU as if for a TPU, stages the
    very bits the gather lowering stages, and both still match the numpy
    oracle."""
    refs, jxs, gathered = _drain_case(CASES[case]())
    monkeypatch.setattr(jax_engine, "_platform", lambda: "tpu")
    _, sel, selected = _drain_case(CASES[case]())
    for ref, jx, js in zip(refs, jxs, sel):
        _assert_parity(ref, jx)
        _assert_parity(ref, js)
    assert int(selected[0]["it"]) > 0
    for a, b in zip(gathered, selected):
        assert a.keys() == b.keys()
        for k in a:
            assert a[k].dtype == b[k].dtype and a[k].shape == b[k].shape, k
            assert a[k].tobytes() == b[k].tobytes(), k


def test_wide_spill_case_charges_three_slots_a_step():
    """The wide-spill case does what it is there for: some prefill steps
    hand off three or more prompts at once, so the chunk's clock prefix
    runs over several charged slots."""
    refs, jxs, staged = _drain_case(_case_wide_spill())
    for ref, jx in zip(refs, jxs):
        _assert_parity(ref, jx)
    out = staged[1]
    handoff = out["out_kind"] == jax_engine._EV_HANDOFF
    for i in range(out["out_kind"].shape[0]):
        per_step = np.bincount(out["out_step"][i][handoff[i]])
        assert per_step.max() >= 3, per_step


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_burst_admits_in_several_waves(platform, monkeypatch):
    """A burst wider than the K-entry admission window admits in several
    waves of one loop iteration, under either lowering, and still matches
    the numpy oracle.  A slot frees only at an event, so at most one
    iteration more than the distinct event steps admits anything; the
    decode pool's two rows admit 300 entries twice (150 once), each time
    in ceil(300 / 128) = 3 waves."""
    monkeypatch.setattr(jax_engine, "_platform", lambda: platform)
    refs, jxs, staged = _drain_case(_case_burst())
    for ref, jx in zip(refs, jxs):
        _assert_parity(ref, jx)
    out = staged[0]
    assert jax_engine._window_len(512, 1024) == 128
    admitting = 1 + len(np.unique(out["out_step"][out["out_kind"] > 0]))
    assert admitting == 3
    assert int(out["admit_waves"]) == 6 > admitting


@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_admit_reads_no_whole_queue_a_slot(phase):
    """At the hybrid binding's padded shape (I=8, S=1024, Q=2048) on the
    TPU's lowering, no equation of `admit`, its wave loop's included, has
    an operand or output of I·S·Q elements: the queue is read through a
    K-entry window at each row's head, not looked up over the whole Q
    axis at every slot."""
    I, S, Q = 8, 1024, 2048
    eng = JaxPoolEngine(instances=I, window=8192, n_slots=S, profile=HYBRID,
                        streamed_params=STREAMED, prefill_chunk=512,
                        respect_arrival=True, phase=phase)
    with enable_x64():
        args = {k: jax.ShapeDtypeStruct(
                    (I, Q) if np.ndim(a) == 2 else np.shape(a),
                    np.asarray(a).dtype)
                for k, a in eng._pack(max_iters=1000).items()}
        closed = jax.make_jaxpr(partial(
            jax_engine._drain_one, phase=phase, n_slots_pad=S,
            platform="tpu"))(args)

    def subjaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                x = getattr(x, "jaxpr", x)
                if hasattr(x, "eqns"):
                    yield x

    def walk(jaxpr, scoped):
        for eqn in jaxpr.eqns:
            inner = scoped or "admit" in str(eqn.source_info.name_stack)
            if inner:
                yield eqn
            for sub in subjaxprs(eqn.params):
                yield from walk(sub, inner)

    admit = list(walk(closed.jaxpr, False))
    assert any(e.primitive.name == "while" for e in admit)  # the waves
    sizes = {int(np.prod(getattr(v.aval, "shape", ())))
             for e in admit for v in (*e.invars, *e.outvars)}
    assert I * Q in sizes and I * S * Q not in sizes


# --- the lowered drain's prefix scans ------------------------------------

@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_drain_scans_only_int32(phase, platform):
    """At the hybrid binding's padded shape (I=8, S=1024, Q=2048) every
    prefix scan of the lowered drain is over int32: a float64 or int64
    `cumsum` lowers to a reduce_window that the TPU emulates in O(S^2)
    adds of 32-bit pairs, every loop iteration."""
    I, S, Q = 8, 1024, 2048
    eng = JaxPoolEngine(instances=I, window=8192, n_slots=S, profile=HYBRID,
                        streamed_params=STREAMED, prefill_chunk=512,
                        respect_arrival=True, phase=phase)
    with enable_x64():
        args = {k: jax.ShapeDtypeStruct(
                    (I, Q) if np.ndim(a) == 2 else np.shape(a),
                    np.asarray(a).dtype)
                for k, a in eng._pack(max_iters=1000).items()}
        text = jax_engine._drain.lower(args, phase=phase, n_slots_pad=S,
                                       platform=platform).as_text()
    # the operand type of every reduce_window, which `cumsum` lowers to
    ops = re.findall(r'"stablehlo\.reduce_window"\(.*?\}\) : '
                     r'\((tensor<[^>]*>)', text, re.S)
    assert ops                       # admit's free-slot ranks at least
    assert all(o.endswith("xi32>") for o in ops), ops


# --- the two lowerings of a row lookup, value by value -------------------

LOOKUP_SHAPES = [(32, 64, 512), (256, 8, 256), (16, 8, 64), (7, 5, 13)]


def _lookup_values(rng, kind, shape):
    """Random values of `kind` with its edge values mixed in: -0.0, +-inf
    and nan for f64; the escalation sentinel and the int32 extremes."""
    if kind == "bool":
        return rng.random(shape) < 0.5
    hot = rng.random(shape) < 0.3
    if kind == "f64":
        v = rng.standard_normal(shape) * 1e3
        edge = np.array([-0.0, 0.0, np.inf, -np.inf, np.nan])
    else:
        info = np.iinfo(np.int32)
        v = rng.integers(info.min, info.max, shape, dtype=np.int32,
                         endpoint=True)
        edge = np.array([_NEVER, info.min, info.max, 0, -1], np.int32)
    v[hot] = rng.choice(edge, int(hot.sum()))
    v[:, :len(edge)] = edge              # each row's first five
    return v


@pytest.mark.parametrize("shape", LOOKUP_SHAPES,
                         ids=["x".join(map(str, s)) for s in LOOKUP_SHAPES])
@pytest.mark.parametrize("kind", ["f64", "i32", "bool", "rank"])
def test_select_lookup_matches_gather_bit_for_bit(kind, shape):
    """The select lowering against the gather in each direction the
    drain reads — emit's (I, S) slots at (I, Q) entries, admit's (I, Q)
    queue at (I, S) slots, the prefill sort's (I, S) at (I, S) — and the
    compare-form inverse map against `vmap(searchsorted)`, ranks past the
    last free slot and rows with no free slot included."""
    I, S, Q = shape
    rng = np.random.default_rng(I * S * Q)
    with enable_x64():
        if kind == "rank":
            free = rng.random((I, S)) < 0.5
            free[::3] = False                     # rows with no free slot
            cum = np.cumsum(free, axis=1, dtype=np.int32)
            qpos = rng.integers(0, Q, I)
            ranks = (np.arange(Q)[None, :] - qpos[:, None] + 1
                     ).astype(np.int32)
            assert (ranks > cum[:, -1:]).any() and (ranks <= 0).any()
            got = np.asarray(jax.jit(partial(
                jax_engine._rank_rows, platform="tpu"))(cum, ranks))
            want = np.asarray(jax.jit(jax.vmap(jnp.searchsorted))(cum, ranks))
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)
            return
        select = jax.jit(partial(jax_engine._take_rows, platform="tpu"))
        gather = jax.jit(partial(jax_engine._take_rows, platform="cpu"))
        for n, m in ((S, Q), (Q, S), (S, S)):
            v = _lookup_values(rng, kind, (I, n))
            idx = rng.integers(0, n, (I, m)).astype(np.int32)
            idx[:, :5] = np.arange(5)            # ... are looked up
            got, want = np.asarray(select(v, idx)), np.asarray(gather(v, idx))
            assert got.dtype == want.dtype and got.shape == (I, m)
            assert got.tobytes() == want.tobytes(), (n, m)
            if kind == "f64":
                assert np.isnan(got[:, 4]).all()
                assert np.signbit(got[:, 0]).all() and (got[:, 0] == 0).all()


def test_jax_fleet_matches_numpy_fleet_seed_numbers():
    """End-to-end anchor: `simulate_spec(engine="jax")` reproduces the
    numpy fleet's committed seed cell (Azure fleetopt, 1000 requests,
    seed 0) to the rounding the baseline records."""
    from repro.core.topospec import TopologySpec
    from repro.serving import simulate_spec
    spec = TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=4096)
    cell = simulate_spec(spec, AZURE, n_requests=1000, seed=0, engine="jax")
    f = cell.report["fleet"]
    assert f["completed"] == 1000
    assert round(cell.sim_decode_tok_per_watt, 2) == 5.66
    assert round(cell.sim_tok_per_watt, 2) == 1.81


# --- property test: random streams, numpy oracle vs JAX ------------------

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

    @settings(max_examples=15, deadline=None)
    @given(streams=st.lists(request_lists, min_size=1, max_size=3),
           n_slots=st.integers(1, 4),
           chunk=st.sampled_from([64, 256]),   # 0 = unchunked: unsupported
           window=st.sampled_from([512, 4096]),
           evict=st.booleans())
    def test_property_numpy_and_jax_step_identically(
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
        ref, jx = _run_both(reqs_by_inst, window=window, n_slots=n_slots,
                            prefill_chunk=chunk, evict_on_overflow=evict)
        _assert_parity(ref, jx)
else:                                                  # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed (requirements-dev)")
    def test_property_numpy_and_jax_step_identically():
        pass
