"""Drain host side: host ms per scenario in the program's spans around each
compiled group's device call, less the wait on the device: `drain.stack`
(padded host arrays), `drain.launch` (transfer and dispatch), `drain.fetch`
(device to host) and `drain.split` (results onto each engine)."""
from . import per_scenario_ms
from ..program_trace import span_seconds

NAMES = ("drain.stack", "drain.launch", "drain.fetch", "drain.split")


def read(ctx):
    return per_scenario_ms(ctx, span_seconds(
        ctx.get("program_spans") or [], NAMES, self_time=True))
