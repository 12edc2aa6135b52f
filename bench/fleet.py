"""One unit of work of a cell on the system under test, the same on the
reference, and the answers both give.

The unit is what the measured window repeats back to back: for every
scenario of the cell `serving.prepare_spec(spec, workload, trace=...,
engine="jax")`, then one `serving.run_fleet_grid` over all of them, which
drains every pool group in the compiled `lax.while_loop` and builds the
reports.  It ends with the results on the host (`drain_engines` blocks on
the drain's outputs).

Every call drains a fresh deal of the cell's traces (`Deals`), so no call
can reuse another's result.  The reference (`bench.plainref`) runs a call's
triples scenario by scenario.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Sequence

import numpy as np

from . import cells, plainref, sampler

# per-instance meter rows of each pool's engine and bank
FLOAT_ROWS = ("joules", "idle_joules", "prefill_joules", "dispatch_joules",
              "handoff_joules", "m_joules", "m_prefill_joules",
              "m_idle_joules", "m_dispatch_joules", "m_handoff_joules",
              "sim_time_s")
ENGINE_FLOAT_ROWS = ("slot_seconds", "m_slot_seconds")
INT_ROWS = ("tokens", "m_tokens", "prefill_tokens")
ENGINE_INT_ROWS = ("preempted", "n_escalated")


@dataclasses.dataclass
class Side:
    """A cell's objects built under the program's root package."""
    spec: Any
    workload: Any
    prefill_chunk: int
    fleetsim: Any          # the program's serving.fleetsim module


def side(cell: Dict[str, Any]) -> Side:
    fleetsim = cells.resolve(cells.PROGRAM_ROOT, "serving.fleetsim")
    return Side(fleetsim=fleetsim, **cells.cell_objects(cell))


def prepare(s: Side, seeds: Sequence[int], traces) -> list:
    # looked up on the module at each call, so wrappers the traced run
    # installs are the ones called
    return [s.fleetsim.prepare_spec(
        s.spec, s.workload, n_requests=len(tr), seed=seed, trace=tr,
        engine="jax", prefill_chunk=s.prefill_chunk)
        for seed, tr in zip(seeds, traces)]


def run_program(prog: Side, seeds, traces) -> list:
    """The unit of work on the system under test: prepare every scenario,
    drain them in one grid call.  Returns the scenarios and their
    reports."""
    scenarios = prepare(prog, seeds, traces)
    results = prog.fleetsim.run_fleet_grid(scenarios)
    return [(sim, reqs, r.report)
            for (sim, reqs, _), r in zip(scenarios, results)]


def reference(cell: Dict[str, Any]) -> dict:
    """The plain reference's view of the cell (`plainref.deployment`)."""
    return plainref.deployment(cell["config_data"],
                               plainref.load_sizing(cell["name"]),
                               cell["traffic_data"])


def max_window(cell: Dict[str, Any]) -> int:
    """The largest pool window of the cell's stated sizing: the bound its
    traces are clipped to."""
    pools = plainref.load_sizing(cell["name"])["pools"]
    return max(p["window"] for p in pools)


def run_reference(d: dict, traces) -> List[Dict[str, Any]]:
    return [plainref.run(d, tr) for tr in traces]


class Deals:
    """The traces of a run's calls: call `j` drains deal `j` of the base
    traces (`sampler.deal_call`)."""

    def __init__(self, cell: Dict[str, Any], max_total: int, seed: int):
        traffic = cell["traffic_data"]
        self.seed = seed
        self.max_total = max_total
        self.seeds = sampler.scenario_seeds(seed, traffic["scenarios"])
        self.bases = sampler.base_traces(traffic, max_total)
        self.n_requests = sum(len(b) for b in self.bases)
        self.dealt = 0

    def __iter__(self) -> Iterator[list]:
        return self

    def __next__(self) -> list:
        self.dealt += 1
        return sampler.deal_call(self.bases, self.seed, self.dealt - 1,
                                 self.max_total)


def _num(v) -> bool:
    return isinstance(v, (int, float, np.integer, np.floating)) \
        and not isinstance(v, bool)


def flat_report(report: Dict[str, dict]) -> Dict[str, Any]:
    """`{"<pool or fleet>.<key>": value}` over the report's numbers."""
    out = {}
    for part, d in report.items():
        for k, v in d.items():
            if isinstance(v, (tuple, list)):
                for i, x in enumerate(v):
                    out[f"{part}.{k}.{i}"] = x
            elif _num(v):
                out[f"{part}.{k}"] = v
            else:
                out[f"{part}.{k}"] = str(v)
    return out


def answers(sim, reqs, report) -> Dict[str, Any]:
    """Everything one scenario's run produced that the comparison reads:
    per request (in rid order) the pool it ended in, its counts and its
    times; per pool the sizing and every meter row; the report."""
    reqs = sorted(reqs, key=lambda r: r.rid)
    ready = [np.nan if r.ready_time is None else r.ready_time for r in reqs]
    req_int = np.array([[r.n_generated, r.preemptions, r.escalations,
                         int(r.finish_time >= 0), int(r.prefill_done)]
                        for r in reqs], np.int64)
    req_time = np.array([[r.first_token_time, r.finish_time, q]
                         for r, q in zip(reqs, ready)], np.float64)
    pools = {}
    for role in sim.order:
        eng = sim.groups[role].engine
        b = eng.bank
        pools[role] = dict(
            shape=np.array([eng.instances, eng.n_slots, eng.window],
                           np.int64),
            floats={k: np.asarray(getattr(b, k), np.float64).copy()
                    for k in FLOAT_ROWS}
            | {k: np.asarray(getattr(eng, k), np.float64).copy()
               for k in ENGINE_FLOAT_ROWS},
            ints={k: np.asarray(getattr(b, k), np.int64).copy()
                  for k in INT_ROWS}
            | {k: np.asarray(getattr(eng, k), np.int64).copy()
               for k in ENGINE_INT_ROWS})
    return dict(rid=np.array([r.rid for r in reqs], np.int64),
                pool=np.array([r.pool for r in reqs]),
                req_int=req_int, req_time=req_time,
                horizon=max((r.arrival_time for r in reqs), default=0.0),
                order=list(sim.order), pools=pools,
                report=flat_report(report))


def call_answers(runs) -> List[Dict[str, Any]]:
    return [answers(sim, reqs, rep) for sim, reqs, rep in runs]
