"""What the program's own instrumentation gives the benchmark
(bench/program_trace.py and the readers of its spans, counters and loop
phases): the scope reduction on a small hand-made trace, each reader on a
synthetic context, and the program's span names on the profiler's host
plane where `trace.load` keeps them."""
import contextlib
import os
import sys
import tempfile

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import program_trace as pt  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.metrics import reader  # noqa: E402

DEV = "/device:TPU:0"
BODY = "jit(_drain_one)/while/body"

# one device, ns: a drain program [1000, 9000] whose `while` op spans its
# body's ops, then another program's op; events are named by their HLO
# instruction text, as the v5e trace names them
OPS = [("%while.1 = (s32[]) while(%t)", 1000, 8000),
       ("%fusion.2 = s32[16]{0} fusion(%a, %b), kind=kCustom", 1500, 4500),
       ("%fusion.3 = f32[4]{0} fusion(%c)", 4500, 5000),
       ("%fusion.4 = s32[4]{0} fusion(%d)", 5000, 6000),
       ("%fusion.5 = pred[] fusion(%e)", 8000, 8600),
       ("%fusion.6 = f32[4]{0} fusion(%f)", 9500, 9900)]
MODULES = [("jit__drain_one", 900, 9000), ("jit_other", 9400, 10000)]
# the compiled program's text: instruction name and op_name metadata
HLO = f"""
  %fusion.2 = s32[16]{{0}} fusion(%a, %b), kind=kCustom, calls=%fc.1, \
metadata={{op_name="{BODY}/decode_step/emit/jit(take_along_axis)/gather"}}
  %fusion.3 = f32[4]{{0}} fusion(%c), metadata={{op_name="{BODY}/decode_step/mul" stack_frame_id=2}}
  %fusion.4 = s32[4]{{0}} fusion(%d), metadata={{op_name="{BODY}/admit/vmap(jit(searchsorted))/vmap()/while/body/closed_call/gather"}}
  ROOT %fusion.5 = pred[] fusion(%e), metadata={{op_name="jit(_drain_one)/while/cond/cond/lt"}}
  %while.1 = (s32[]) while(%t), condition=%c.1, body=%b.1, metadata={{op_name="jit(_drain_one)/while"}}
  %fusion.6 = f32[4]{{0}} fusion(%f), metadata={{op_name="jit(other)/decode_step/emit/add"}}
  %constant.7 = s32[] constant(0)
"""


def test_hlo_scopes_and_instruction_names():
    sc = pt.hlo_scopes(HLO)
    assert set(sc) == {"fusion.2", "fusion.3", "fusion.4", "fusion.5",
                       "while.1", "fusion.6"}
    assert sc["fusion.5"] == "jit(_drain_one)/while/cond/cond/lt"
    assert pt.instruction(OPS[1][0]) == "fusion.2"
    assert pt.instruction("fusion.9") == "fusion.9"


def test_phase_of_takes_the_innermost_phase():
    sc = pt.hlo_scopes(HLO)
    assert pt.phase_of(sc["fusion.2"]) == "emit"
    assert pt.phase_of(sc["fusion.3"]) == "decode_step"
    assert pt.phase_of(sc["fusion.4"]) == "admit"
    assert pt.phase_of(sc["fusion.5"]) == "cond"
    assert pt.phase_of(sc["while.1"]) == pt.UNSCOPED
    assert pt.phase_of("") == pt.UNSCOPED


def test_only_the_drain_programs_ops_count():
    got = pt.drain_ops(OPS, MODULES)
    assert [pt.instruction(n) for n, _, _ in got] == [
        "while.1", "fusion.2", "fusion.3", "fusion.4", "fusion.5"]
    assert pt.drain_ops(OPS, [("jit_other", 0, 20000)]) == []


def test_phase_self_times_on_a_small_trace():
    scoped = dict(ops={DEV: pt.drain_ops(OPS, MODULES)})
    got = pt.phase_times(scoped, 0, 10000, pt.hlo_scopes(HLO))
    # the while op less its body's ops: 7000 - 3000 - 500 - 1000
    assert got["phase_s"] == pytest.approx(
        {"emit": 3e-6, "decode_step": 0.5e-6, "admit": 1e-6,
         pt.UNSCOPED: 2.5e-6, "cond": 0.6e-6})
    assert got["op_phase"][tr.short_name(OPS[1][0])] == "emit"
    # clipped to the window; a second device averages
    scoped["ops"]["/device:TPU:1"] = [(OPS[1][0], 2000, 3000)]
    got = pt.phase_times(scoped, 2000, 10000, pt.hlo_scopes(HLO))
    assert got["phase_s"]["emit"] == pytest.approx((2.5e-6 + 1e-6) / 2)


def test_a_program_without_scopes_gives_no_emit():
    scoped = dict(ops={DEV: pt.drain_ops(OPS, MODULES)})
    ph = pt.phase_times(scoped, 0, 10000, {})["phase_s"]
    assert set(ph) == {pt.UNSCOPED}
    ctx = dict(phases=ph, scenarios=2)
    assert reader("emit_device_ms")(ctx) is None


SPANS = [  # [name, start_ns, end_ns, parent, args]
    ["fleet.prepare", 0, 10_000_000, None, {}],
    ["prepare.build", 0, 6_000_000, 0, {}],
    ["prepare.requests", 7_000_000, 9_000_000, 0, {}],
    ["grid.stage", 10_000_000, 60_000_000, None, {"k": 0}],
    ["drain.group", 11_000_000, 50_000_000, 3, {"phase": "decode"}],
    ["drain.stack", 11_000_000, 12_000_000, 4, {}],
    ["drain.launch", 12_000_000, 13_000_000, 4, {}],
    ["drain.wait", 13_000_000, 48_000_000, 4, {}],
    ["drain.fetch", 48_000_000, 49_000_000, 4, {}],
    ["drain.split", 49_000_000, 49_500_000, 4, {}],
]


def _ctx(**kw):
    ctx = dict(spans=[], counters={}, trace={}, scenarios=2,
               program_spans=SPANS,
               program_counters={"drain.entry_iters": 10_000,
                                 "drain.entry_iters_padded": 65_536},
               phases={"emit": 0.4, "decode_step": 0.1})
    ctx.update(kw)
    return ctx


@pytest.mark.parametrize("name, want", [
    ("emit_device_ms", 200.0),
    ("drain_queue_fill", 100 * 10_000 / 65_536),
    ("drain_host_ms", 3.5 / 2),
    ("sizing_ms", 3.0),
    ("request_build_ms", 1.0),
])
def test_readers_on_a_synthetic_context(name, want):
    assert reader(name)(_ctx()) == pytest.approx(want)
    # a program without the recorder (an older commit) reads nothing
    assert reader(name)(dict(spans=[], counters={}, trace={},
                             scenarios=2)) is None


def test_span_seconds_self_time():
    assert pt.span_seconds(SPANS, ["drain.group"]) == pytest.approx(39e-3)
    assert pt.span_seconds(SPANS, ["drain.group"], self_time=True) == \
        pytest.approx(39e-3 - 38.5e-3)
    assert pt.span_seconds(SPANS, ["fleet.prepare"], self_time=True) == \
        pytest.approx(2e-3)
    assert pt.span_seconds(SPANS, ["nothing"]) is None


def test_start_offsets_pair_spans_with_host_events_by_rank():
    spans = [["a", 1_000_100, 0, None, {}], ["a", 1_000_900, 0, None, {}],
             ["b", 1_000_500, 0, None, {}]]
    host = {"a": [(850, 880), (130, 140)], "b": []}
    missing, worst = pt.start_offsets(spans, host, 1_000_000)
    assert missing == 1 and worst == 50
    assert pt.start_offsets(spans, host, None)[0] == 3


def test_recorder_of_a_program_without_one_yields_none():
    class Old:
        pass
    with pt.recorder(lambda m: Old) as rec:
        assert rec is None
    from repro.serving import telemetry
    with pt.recorder(lambda m: telemetry) as rec:
        assert rec is telemetry._host is not None
    assert telemetry._host is None


def test_program_spans_reach_the_host_events_trace_load_keeps():
    """The program's spans, opened under the profiler on the CPU, are on
    its host plane under their own names (arguments ride as stats), at the
    recorder's times, and `trace.load` keeps them when asked."""
    import jax
    from repro.core.modelspec import LLAMA31_70B
    from repro.core.profiles import H100_LLAMA70B
    from repro.core.topospec import TopologySpec
    from repro.core.workloads import AZURE
    from repro.serving import prepare_spec, telemetry

    spec = TopologySpec.from_kind("fleetopt", H100_LLAMA70B, LLAMA31_70B,
                                  b_short=4096)
    with tempfile.TemporaryDirectory() as d:
        with contextlib.ExitStack() as stack:
            rec = stack.enter_context(telemetry.host_tracing())
            jax.profiler.start_trace(d)
            stack.callback(jax.profiler.stop_trace)
            prepare_spec(spec, AZURE, n_requests=50, seed=0)
            with telemetry.host_span("drain.group", phase="decode", rows=3):
                pass
        names = {s[0] for s in rec.spans}
        assert names == {"fleet.prepare", "prepare.build", "prepare.sim",
                         "prepare.requests", "drain.group"}
        events = tr.load(d, names)
        scoped = pt.load(d, names)
    assert sorted(n for n, _, _ in events["host"]) == sorted(
        s[0] for s in rec.spans)
    missing, worst = pt.start_offsets(rec.spans, scoped["host"],
                                      scoped["start_ns"])
    # same clock: microseconds apart (a wrong clock is seconds or more off)
    assert missing == 0 and worst < 50e6
