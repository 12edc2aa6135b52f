"""Per-layer metric readers, one module per metric of `BENCHMARK.json`.

Each module `bench/metrics/<metric>.py` has `read(ctx) -> float | None`.
`ctx` holds what the traced run recorded:

  spans      [(name, start_s, end_s)] of the wrapped program methods
             (`bench.spans.WRAPPED`), host clock;
  counters   {"drain_iters": ..., "drain_groups": ...} read from the drain;
  trace      `bench.trace.reduce_trace` of the profiler's trace ({} when
             it held no device);
  scenarios  scenarios drained in the traced window (calls x scenarios).

A reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

import importlib
from typing import Optional


def reader(name: str):
    return importlib.import_module(f"{__name__}.{name}").read


def span_total(ctx: dict, name: str) -> Optional[float]:
    """Seconds spent in calls of one wrapped method; None if it never ran."""
    ds = [b - a for n, a, b in ctx["spans"] if n == name]
    return sum(ds) if ds else None


def per_scenario_ms(ctx: dict, seconds: Optional[float]) -> Optional[float]:
    if seconds is None or not ctx["scenarios"]:
        return None
    return 1e3 * seconds / ctx["scenarios"]
