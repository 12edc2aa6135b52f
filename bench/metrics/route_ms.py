"""Routing: host ms per scenario in `FleetSim.begin_run` (every request
routed to a pool instance, measurement window set)."""
from . import per_scenario_ms, span_total


def read(ctx):
    return per_scenario_ms(ctx, span_total(ctx, "FleetSim.begin_run"))
