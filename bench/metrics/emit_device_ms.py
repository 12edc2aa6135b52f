"""Device drain, event emission: device ms per scenario of the drain's ops
under its `emit` scope (self time), from the profile
(`bench.program_trace.phase_times`)."""
from . import per_scenario_ms


def read(ctx):
    return per_scenario_ms(ctx, ctx.get("phases", {}).get("emit") or None)
