"""The comparison that decides `correct`.

One call of the window, drawn from the seed, is compared with the plain
reference (`bench.plainref`) on the same triples.  Each number has
its own limit; `PERF.md` gives the
readings each limit was set from (sound float64 drains on the chip, and the
float32 control).

  pool_mismatch   requests whose final pool and instance differ (routing,
                  overflow migration).
  count_mismatch  integers that differ: per request the tokens generated,
                  preemptions, escalations, completion and handoff flags;
                  per pool its sizing and the token, preemption and
                  escalation counters of every instance; every string and
                  integer of the report.
  time_rel        largest gap of a request's first-token, finish or
                  ready time, over the scenario's arrival horizon.
  meter_rel       largest gap of a pool instance's float meter: an energy
                  meter (total, idle, prefill, dispatch, handoff; lifetime
                  and measured) over the instance's total joules, its
                  clock and slot-seconds over their own value.  An idle
                  meter is a sum of clock differences, so an instance
                  that idled a few microseconds would otherwise weigh the
                  clock's last-digit rounding as a large error.
  report_rel      largest relative gap of a float in the fleet and pool
                  reports (tok/W, latency percentiles, ...).
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

# Limits.  The exact comparisons are 0.  The relative ones sit between the
# largest reading of sound float64 drains on the chip and the smallest
# reading of the float32 control (PERF.md, "How correct is decided").
LIMITS = {
    "pool_mismatch": 0,
    "count_mismatch": 0,
    "time_rel": 1e-8,
    "meter_rel": 1e-8,
    "report_rel": 1e-8,
}


def _gap(a: np.ndarray, b: np.ndarray, scale: np.ndarray) -> float:
    """Largest |a_i - b_i| / scale_i; a gap against a zero scale, or a NaN
    on one side only, is infinite."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape or (np.isnan(a) != np.isnan(b)).any():
        return float("inf")
    if a.size == 0:
        return 0.0
    diff = np.abs(np.nan_to_num(a - b, nan=0.0))
    scale = np.abs(np.broadcast_to(scale, diff.shape))
    with np.errstate(divide="ignore", invalid="ignore"):
        rel = np.where(diff == 0, 0.0,
                       np.where(scale > 0, diff / scale, np.inf))
    return float(rel.max())


def meter_gaps(got: dict, ref: dict) -> Dict[str, float]:
    """`meter_rel` of one pool, per meter."""
    energy = np.asarray(ref["floats"]["joules"], np.float64)
    return {k: _gap(got["floats"][k], v, energy if k.endswith("joules")
                    else np.abs(np.asarray(v, np.float64)))
            for k, v in ref["floats"].items()}


def _mismatch(a: np.ndarray, b: np.ndarray) -> int:
    if a.shape != b.shape:
        return max(a.size, b.size, 1)
    return int((a != b).sum())


def compare_scenario(got: dict, ref: dict) -> Dict[str, float]:
    """The numbers for one scenario (program `got` against reference)."""
    out = dict(pool_mismatch=0, count_mismatch=0, time_rel=0.0,
               meter_rel=0.0, report_rel=0.0)
    if not np.array_equal(got["rid"], ref["rid"]):
        out["count_mismatch"] += max(len(got["rid"]), len(ref["rid"]))
        out["pool_mismatch"] = out["count_mismatch"]
        return out
    out["pool_mismatch"] = _mismatch(got["pool"], ref["pool"])
    out["count_mismatch"] += _mismatch(got["req_int"], ref["req_int"])
    horizon = max(ref["horizon"], 1e-9)
    tg, tr = got["req_time"], ref["req_time"]
    if tg.shape != tr.shape or (np.isnan(tg) != np.isnan(tr)).any():
        out["time_rel"] = float("inf")
    else:
        d = np.abs(np.nan_to_num(tg - tr, nan=0.0))
        out["time_rel"] = float(d.max() / horizon) if d.size else 0.0
    if got["order"] != ref["order"]:
        out["count_mismatch"] += 1 + abs(len(got["order"])
                                         - len(ref["order"]))
    for role in ref["order"]:
        if role not in got["pools"]:
            out["count_mismatch"] += 1
            continue
        pg, pr = got["pools"][role], ref["pools"][role]
        out["count_mismatch"] += _mismatch(pg["shape"], pr["shape"])
        for k, v in pr["ints"].items():
            out["count_mismatch"] += _mismatch(pg["ints"][k], v)
        out["meter_rel"] = max(out["meter_rel"],
                               *meter_gaps(pg, pr).values())
    rg, rr = got["report"], ref["report"]
    for k in set(rg) | set(rr):
        a, b = rg.get(k), rr.get(k)
        if a is None or b is None or isinstance(a, str) \
                or isinstance(b, str) or isinstance(b, (int, np.integer)):
            out["count_mismatch"] += int(a != b)
        else:
            out["report_rel"] = max(out["report_rel"],
                                    _gap(a, b, np.abs(np.float64(b))))
    return out


def compare(got: List[dict], ref: List[dict]) -> Dict[str, float]:
    """The numbers for a call of several scenarios: the worst over them."""
    out = dict(pool_mismatch=0, count_mismatch=0, time_rel=0.0,
               meter_rel=0.0, report_rel=0.0)
    if len(got) != len(ref):
        out["count_mismatch"] += abs(len(got) - len(ref))
    for g, r in zip(got, ref):
        for k, v in compare_scenario(g, r).items():
            out[k] = out[k] + v if k.endswith("mismatch") else max(out[k], v)
    return out


def verdict(numbers: Dict[str, float]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def checks(numbers: Dict[str, float]) -> Dict[str, dict]:
    """Each number beside its limit, for the result line."""
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
