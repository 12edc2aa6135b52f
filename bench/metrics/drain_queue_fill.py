"""Device drain, padding: percent of the queue entries the drain's loop
gathers over that hold a request, iteration-weighted: 100 x the program's
counter `drain.entry_iters` (it x real entries, per compiled group) over
`drain.entry_iters_padded` (it x the padded instance x queue grid)."""


def read(ctx):
    c = ctx.get("program_counters") or {}
    real = c.get("drain.entry_iters")
    padded = c.get("drain.entry_iters_padded")
    if real is None or not padded:
        return None
    return 100.0 * real / padded
