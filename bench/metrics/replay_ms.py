"""Replay: host ms per scenario in `JaxPoolEngine._finalize` (the drain's
terminal events replayed onto the requests and meters)."""
from . import per_scenario_ms, span_total


def read(ctx):
    return per_scenario_ms(ctx, span_total(ctx, "JaxPoolEngine._finalize"))
