"""Pack: host ms per scenario in `JaxPoolEngine._pack` (queues frozen into
the drain's device-ready arrays)."""
from . import per_scenario_ms, span_total


def read(ctx):
    return per_scenario_ms(ctx, span_total(ctx, "JaxPoolEngine._pack"))
