"""Spec to sim, sizing: host ms per scenario in the program's span
`prepare.build` (`TopologySpec.build`: analytical sizing, router policy,
model registry)."""
from . import per_scenario_ms
from ..program_trace import span_seconds


def read(ctx):
    return per_scenario_ms(ctx, span_seconds(
        ctx.get("program_spans") or [], ["prepare.build"]))
