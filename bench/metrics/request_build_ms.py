"""Spec to sim, requests: host ms per scenario in the program's span
`prepare.requests` (`trace_requests`: the scenario's `Request` objects)."""
from . import per_scenario_ms
from ..program_trace import span_seconds


def read(ctx):
    return per_scenario_ms(ctx, span_seconds(
        ctx.get("program_spans") or [], ["prepare.requests"]))
