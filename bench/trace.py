"""From the profiler's trace to the numbers the per-layer readers take.

`load(profile_dir)` reads the `.xplane.pb` that `jax.profiler` wrote and
keeps three kinds of event, each `(name, start_ns, end_ns)` on the trace's
own clock:

  ops      operations that ran on a device: the `XLA Ops` line of every
           `/device:` plane (keyed by plane);
  modules  whole programs on a device: its `XLA Modules` line;
  host     the benchmark's host spans (`bench.*` and the program methods
           `bench.spans` wraps), from the host plane.

`reduce_trace` turns them into busy and idle time inside the traced window,
the device time of the drain programs, the operations that took most time
(self time: a loop op less the ops of its body), and the idle gaps by the
host span that was open.  It is plain arithmetic on
those lists, so a test checks it on a small recorded trace.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

WINDOW_SPAN = "bench.window"
CALL_SPAN = "bench.call"
# the compiled drain's program name: `jax.jit(_drain_one)` in
# serving.jax_engine lowers to a module `jit__drain_one`
DRAIN_PROGRAM = "_drain_one"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(profile_dir: str, host_names: Iterable[str]) -> dict:
    """The events of the newest trace under `profile_dir`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    wanted = set(host_names) | {WINDOW_SPAN, CALL_SPAN}
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dest = modules.setdefault(plane.name, [])
                else:
                    continue
                dest.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in wanted)
    return dict(ops=ops, modules=modules, host=host)


def union(intervals: Iterable[Tuple[float, float]], lo: float = -1e300,
          hi: float = 1e300) -> List[Tuple[float, float]]:
    """Merged, sorted intervals, clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def window(events: dict) -> Optional[Tuple[float, float]]:
    spans = [(a, b) for n, a, b in events["host"] if n == WINDOW_SPAN]
    if spans:
        return min(a for a, _ in spans), max(b for _, b in spans)
    every = [(a, b) for evs in events["ops"].values() for _, a, b in evs]
    if not every:
        return None
    return min(a for a, _ in every), max(b for _, b in every)


def self_times(evs: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Device time per operation name inside [lo, hi], less the time of the
    operations nested in it (a `while` op spans its body's ops on the same
    line), so nothing is counted twice."""
    out: Dict[str, float] = {}
    stack: List[list] = []          # [name, end, self time so far]

    def close(e):
        out[e[0]] = out.get(e[0], 0.0) + e[2]

    for n, a, b in sorted(evs, key=lambda e: (e[1], -e[2])):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([n, b, b - a])
    while stack:
        close(stack.pop())
    return out


def short_name(op: str, width: int = 120) -> str:
    """An HLO op's event name up to `width` characters (the instruction
    name, its result type and the start of its operands)."""
    return op if len(op) <= width else op[:width - 3] + "..."


def host_timeline(host: List[Event]) -> Tuple[List[float], List[str]]:
    """Elementary segments between host span boundaries, each labelled with
    the shortest span open over it (the one deepest in the call stack), or
    "no span".  Segment k is [bounds[k], bounds[k + 1])."""
    bounds = sorted({t for _, a, b in host for t in (a, b)})
    labels = []
    for a, b in zip(bounds, bounds[1:]):
        mid = 0.5 * (a + b)
        best = None
        for n, s0, s1 in host:
            if s0 <= mid < s1 and (best is None or s1 - s0 < best[1]):
                best = (n, s1 - s0)
        labels.append(best[0] if best else "no span")
    return bounds, labels


def attribute(gaps, host: List[Event]) -> Dict[str, float]:
    """Split every gap over the host spans open during it: ns per span."""
    bounds, labels = host_timeline(host)
    out: Dict[str, float] = {}
    for a, b in gaps:
        t = a
        while t < b:
            k = bisect.bisect_right(bounds, t) - 1   # segment holding t
            if 0 <= k < len(labels):
                name, nxt = labels[k], bounds[k + 1]
            else:
                name = "no span"
                nxt = bounds[0] if k < 0 and bounds else b
            nxt = min(nxt, b)
            out[name] = out.get(name, 0.0) + (nxt - t)
            t = nxt
    return out


def reduce_trace(events: dict, top: int = 10) -> dict:
    """Seconds of the traced window, busy time averaged over the devices
    that ran anything, drain-program device time, the `top` operations by
    device time and the `top` host spans by the idle time they were open
    over."""
    win = window(events)
    if win is None:
        return {}
    lo, hi = win
    devices = {p: evs for p, evs in events["ops"].items() if evs}
    if not devices:
        return dict(window_s=(hi - lo) * 1e-9)
    busy_ns, drain_ns = [], []
    op_time: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for plane, evs in devices.items():
        busy = union(((a, b) for _, a, b in evs), lo, hi)
        busy_ns.append(length(busy))
        prev = lo
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = b
        if hi > prev:
            gaps.append((prev, hi))
        for n, t in self_times(evs, lo, hi).items():
            op_time[n] = op_time.get(n, 0.0) + t
        mods = events["modules"].get(plane, [])
        drain_ns.append(length(union(
            ((a, b) for n, a, b in mods if DRAIN_PROGRAM in n), lo, hi)))
    n_dev = len(devices)
    host = [e for e in events["host"] if e[0] != WINDOW_SPAN]
    idle_by = {n: t / n_dev for n, t in attribute(gaps, host).items()}
    window_s = (hi - lo) * 1e-9
    return dict(
        window_s=window_s,
        busy_s=sum(busy_ns) / n_dev * 1e-9,
        drain_device_s=sum(drain_ns) / n_dev * 1e-9,
        device_ops=[[short_name(n), t / n_dev * 1e-9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[n, t * 1e-9] for n, t in sorted(
            idle_by.items(), key=lambda kv: -kv[1])[:top]],
        calls=sum(1 for n, _, _ in events["host"] if n == CALL_SPAN))
