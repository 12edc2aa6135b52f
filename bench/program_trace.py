"""What the program's own instrumentation left in a traced run.

The program records its host layers itself (`serving.telemetry`'s host
channel: `host_tracing()`, `host_span`, `host_count`) and names each phase
of the compiled drain's loop with `jax.named_scope` (`jax_engine._drain_one`:
`idle_skip`, `admit`, `prefill_step`, `decode_step`, `coast`, `emit`,
`cond`).  XLA keeps the scope path in each op's metadata (`op_name`), e.g.
`jit(_drain_one)/while/body/decode_step/emit/jit(take_along_axis)/gather`.
The v5e trace does not carry it: an `XLA Ops` event holds only the
instruction's text (`%fusion.1803 = s32[16384]{...} fusion(...)`) and the
stats `device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`.
So the path comes from the compiled drain's HLO text (`hlo_scopes`), whose
instruction names are the ones the trace shows.

  recorder(resolve)       the program's `host_tracing` context, or a
                          context that yields None where the program has
                          none (an older commit);
  load(profile_dir)       the device ops inside the drain programs, the
                          recorder's host events and the profile's start
                          on the host clock;
  hlo_scopes(hlo_text)    instruction name -> scope path of a compiled
                          program (`jax.jit(f).lower(...).compile()
                          .as_text()`);
  phase_times(...)        the drain ops' device self time per loop phase
                          (the innermost phase on the path), with
                          `trace.self_times`, and the phase of each op;
  start_offsets(...)      how far each recorder span starts from its
                          event on the profiler's host plane.

An op whose path names no phase counts under `UNSCOPED`: every op of a
program without the scopes does.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from . import trace as tr

PHASES = ("idle_skip", "admit", "prefill_step", "decode_step", "coast",
          "emit", "cond")
UNSCOPED = "unscoped"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
ENV_PLANE = "Task Environment"
START_STAT = "profile_start_time"


def recorder(resolve):
    """`serving.telemetry.host_tracing()` of the program `resolve(module)`
    imports; a null context (yielding None) if it has none."""
    tel = resolve("serving.telemetry")
    on = getattr(tel, "host_tracing", None)
    return on() if on is not None else contextlib.nullcontext()


def instruction(op: str) -> str:
    """The HLO instruction name of an op event (`%fusion.1803 = ...` ->
    `fusion.1803`)."""
    m = _INSTRUCTION.match(op)
    return m.group(1) if m else op


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> `op_name` scope path, over every instruction of
    an HLO module's text that carries one."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            p = _OP_NAME.search(line)
            if p:
                out[m.group(1)] = p.group(1)
    return out


def phase_of(path: str) -> str:
    for part in reversed(path.split("/")):
        if part in PHASES:
            return part
    return UNSCOPED


def _newest(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def load(profile_dir: str, host_names=()) -> dict:
    """Drain ops per device plane as `(op, start_ns, end_ns)`, the
    recorder's host events by name (trace clock) and the profile's start
    in ns since the epoch."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(_newest(profile_dir))
    wanted = set(host_names)
    ops: Dict[str, List[tr.Event]] = {}
    host: Dict[str, List[Tuple[float, float]]] = {}
    start = None
    for plane in data.planes:
        if plane.name == ENV_PLANE:
            start = dict(plane.stats).get(START_STAT)
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if tr.OPS_LINE not in lines or tr.MODULES_LINE not in lines:
                continue
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines[tr.OPS_LINE].events]
            ops[plane.name] = drain_ops(evs, [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in lines[tr.MODULES_LINE].events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return dict(ops=ops, host=host,
                start_ns=None if start is None else int(start))


def drain_ops(ops: List[tr.Event], modules: List[tr.Event]
              ) -> List[tr.Event]:
    """The ops of one device that start inside a drain program's run."""
    spans = tr.union((a, b) for n, a, b in modules
                     if tr.DRAIN_PROGRAM in n)
    starts = [a for a, _ in spans]
    out = []
    for e in ops:
        k = bisect.bisect_right(starts, e[1]) - 1
        if k >= 0 and e[1] < spans[k][1]:
            out.append(e)
    return out


def phase_times(scoped: dict, lo: float, hi: float,
                scopes: Dict[str, str]) -> dict:
    """Device self time (s) of the drain ops inside [lo, hi] per loop phase,
    averaged over the devices that ran any, and the phase of each op
    (keyed by `trace.short_name`, as the breakdown names ops); `scopes`
    maps instruction names to scope paths (`hlo_scopes`)."""
    phase = {}
    per_phase: Dict[str, float] = {}
    devices = [evs for evs in scoped["ops"].values() if evs]
    for evs in devices:
        for n, _, _ in evs:
            if n not in phase:
                phase[n] = phase_of(scopes.get(instruction(n), ""))
        named = [(phase[n], a, b) for n, a, b in evs]
        for ph, t in tr.self_times(named, lo, hi).items():
            per_phase[ph] = per_phase.get(ph, 0.0) + t
    n = max(len(devices), 1)
    return dict(
        phase_s={ph: t / n * 1e-9 for ph, t in per_phase.items()},
        op_phase={tr.short_name(op): ph for op, ph in phase.items()})


def start_offsets(spans: list, host: Dict[str, List[Tuple[float, float]]],
                  start_ns: Optional[int]) -> Tuple[int, float]:
    """(recorder spans without a host event, largest |start offset| in ns)
    between each recorder span `[name, start_ns, end_ns, ...]` (ns since
    the epoch) and the profiler's host event of the same name and rank
    (trace clock + the profile's start)."""
    if start_ns is None:
        return len(spans), float("nan")
    mine: Dict[str, List[int]] = {}
    for s in spans:
        mine.setdefault(s[0], []).append(s[1])
    missing, worst = 0, 0.0
    for name, starts in mine.items():
        theirs = sorted(a for a, _ in host.get(name, []))
        missing += max(len(starts) - len(theirs), 0)
        for a, b in zip(sorted(starts), theirs):
            worst = max(worst, abs(a - (start_ns + b)))
    return missing, worst


def span_seconds(spans: list, names, self_time: bool = False
                 ) -> Optional[float]:
    """Seconds in the recorder spans called one of `names`; with
    `self_time`, less the time of the spans opened directly under them.
    None if no such span ran."""
    names = set(names)
    hit = [k for k, s in enumerate(spans) if s[0] in names]
    if not hit:
        return None
    ns = sum(spans[k][2] - spans[k][1] for k in hit)
    if self_time:
        hit = set(hit)
        ns -= sum(s[2] - s[1] for s in spans if s[3] in hit)
    return ns * 1e-9
