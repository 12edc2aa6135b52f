"""Cross-pool flow and report: host ms per scenario in `FleetSim.drain_role`
outside the `_finalize` it calls (outboxes, inboxes, pool summaries), plus
`FleetSim.finish_run` (the fleet report)."""
from . import per_scenario_ms, span_total


def read(ctx):
    drain = span_total(ctx, "FleetSim.drain_role")
    finish = span_total(ctx, "FleetSim.finish_run")
    if drain is None or finish is None:
        return None
    replay = span_total(ctx, "JaxPoolEngine._finalize") or 0.0
    return per_scenario_ms(ctx, drain - replay + finish)
