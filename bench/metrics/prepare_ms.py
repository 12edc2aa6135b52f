"""Spec to sim: host ms per scenario in `serving.fleetsim.prepare_spec`
(analytical sizing, engine construction, request build)."""
from . import per_scenario_ms, span_total


def read(ctx):
    return per_scenario_ms(ctx, span_total(ctx, "prepare_spec"))
