"""The reduction from a profiler trace to busy, idle, op and drain times
(bench/trace.py) on a small recorded trace with known answers."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from bench import trace as tr  # noqa: E402

DEV = "/device:TPU:0"

# a window of 10 us on the trace clock (ns): two drain programs with three
# ops, an idle stretch while the host packs and one while it replays
SMALL = dict(
    ops={DEV: [["fusion.1", 1000, 3000], ["fusion.2", 3000, 4000],
               ["while.3", 6000, 9000], ["fusion.1", 8500, 9000],
               ["fusion.4", 9000, 9500]]},
    modules={DEV: [["jit__drain_one", 900, 4100],
                   ["jit_other", 5900, 6100],
                   ["jit__drain_one", 6100, 9600]]},
    host=[["bench.window", 0, 10000], ["bench.call", 0, 10000],
          ["JaxPoolEngine._pack", 0, 1000],
          ["drain_engines", 0, 9700],
          ["JaxPoolEngine._finalize", 9700, 10000]])


def test_reduction_on_small_trace():
    red = tr.reduce_trace(SMALL)
    assert red["window_s"] == pytest.approx(10e-6)
    # busy: [1000, 4000] and [6000, 9500]
    assert red["busy_s"] == pytest.approx(6.5e-6)
    assert red["drain_device_s"] == pytest.approx((3200 + 3500) * 1e-9)
    ops = dict(red["device_ops"])
    # self time: the while op less the fusion nested in it
    assert ops == pytest.approx({"fusion.1": 2.5e-6, "fusion.2": 1e-6,
                                 "while.3": 2.5e-6, "fusion.4": 0.5e-6})
    assert [n for n, _ in red["device_ops"]][-1] == "fusion.4"
    gaps = dict(red["idle_gaps"])
    # [0,1000] under _pack, [4000,6000] under drain_engines, [9500,9700]
    # under drain_engines, [9700,10000] under _finalize
    assert gaps == pytest.approx({"JaxPoolEngine._pack": 1e-6,
                                  "drain_engines": 2.2e-6,
                                  "JaxPoolEngine._finalize": 0.3e-6})
    assert red["busy_s"] + sum(gaps.values()) == \
        pytest.approx(red["window_s"])
    assert red["calls"] == 1


def test_ops_outside_the_window_do_not_count():
    events = json.loads(json.dumps(SMALL))
    events["ops"][DEV].append(["fusion.9", 20000, 30000])
    events["ops"][DEV].append(["fusion.8", -500, 900])
    red = tr.reduce_trace(events)
    assert red["busy_s"] == pytest.approx(7.4e-6)
    assert dict(red["device_ops"])["fusion.8"] == pytest.approx(0.9e-6)
    assert "fusion.9" not in dict(red["device_ops"])


def test_busy_averages_over_devices():
    events = json.loads(json.dumps(SMALL))
    events["ops"]["/device:TPU:1"] = [["fusion.1", 0, 10000]]
    red = tr.reduce_trace(events)
    assert red["busy_s"] == pytest.approx((6.5e-6 + 10e-6) / 2)


def test_no_device_gives_no_busy():
    red = tr.reduce_trace(dict(ops={}, modules={}, host=SMALL["host"]))
    assert red == {"window_s": pytest.approx(10e-6)}
    assert tr.reduce_trace(dict(ops={}, modules={}, host=[])) == {}


def test_union_merges_and_clips():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)], 0, 7.5) == \
        [(1, 4), (5, 7.5)]
    assert tr.length(tr.union([(0, 1), (0, 1)])) == 1


def test_gaps_outside_every_span_are_no_span():
    gaps = tr.attribute([(0, 10), (40, 60)], [["a", 5, 20], ["b", 45, 50]])
    assert gaps == {"no span": 5 + 5 + 10, "a": 5, "b": 5}
    assert tr.attribute([(0, 10)], []) == {"no span": 10}


def test_nested_ops_count_their_self_time():
    evs = [["while.1", 0, 100], ["fusion.2", 10, 30], ["fusion.3", 40, 60],
           ["while.4", 50, 55], ["fusion.5", 120, 130]]
    got = tr.self_times(evs, 0, 125)
    assert got == {"while.1": 60, "fusion.2": 20, "fusion.3": 15,
                   "while.4": 5, "fusion.5": 5}
    assert sum(got.values()) == tr.length(tr.union(
        (a, b) for _, a, b in evs)) - 5


def test_long_op_names_are_cut():
    name = "%fusion.1 = f32[8]{0} fusion(" + "x" * 300
    assert len(tr.short_name(name)) == 120
    assert tr.short_name("%fusion.1") == "%fusion.1"


def _per_ns(events):
    """An independent count, nanosecond by nanosecond: busy time, and each
    nanosecond given to the shortest op over it (the innermost)."""
    import numpy as np
    (lo, hi), = [(a, b) for n, a, b in events["host"]
                 if n == tr.WINDOW_SPAN]
    span = int(hi - lo)
    busy = np.zeros(span, bool)
    owner = np.full(span, -1)
    width = np.full(span, np.inf)
    ops = next(iter(events["ops"].values()))
    for k, (_, a, b) in enumerate(ops):
        a, b = int(max(a, lo) - lo), int(min(b, hi) - lo)
        if b <= a:
            continue
        busy[a:b] = True
        shorter = width[a:b] > (b - a)
        owner[a:b][shorter] = k
        width[a:b][shorter] = b - a
    self_ns = {}
    for k in owner[owner >= 0]:
        self_ns[ops[k][0]] = self_ns.get(ops[k][0], 0) + 1
    return busy.sum(), self_ns, span


def test_reduction_on_recorded_v5e_excerpt():
    """140 us of the drain's while loop as one TPU v5e recorded it (op
    names cut to the HLO instruction name), checked against a count made
    nanosecond by nanosecond."""
    with open(os.path.join(HERE, "trace_v5e_excerpt.json")) as f:
        events = json.load(f)
    busy_ns, self_ns, span = _per_ns(events)
    red = tr.reduce_trace(events, top=1000)
    assert red["window_s"] == pytest.approx(span * 1e-9)
    assert red["busy_s"] == pytest.approx(busy_ns * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert dict(red["device_ops"]) == pytest.approx(
        {n: t * 1e-9 for n, t in self_ns.items()})
    assert sum(t for _, t in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"])
    assert [n for n, _ in red["idle_gaps"]] == ["drain_engines"]
    (_, a, b), = next(iter(events["modules"].values()))
    assert red["drain_device_s"] == pytest.approx(
        (min(b, span) - max(a, 0)) * 1e-9)
