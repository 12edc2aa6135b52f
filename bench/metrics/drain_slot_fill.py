"""Device drain, padding: percent of the slots the drain's loop selects
over that hold a request, iteration-weighted: 100 x the program's counter
`drain.slot_iters` (occupied slots summed over the loop's iterations, per
compiled group) over `drain.slot_iters_padded` (it x the padded instance x
slot grid)."""


def read(ctx):
    c = ctx.get("program_counters") or {}
    live = c.get("drain.slot_iters")
    padded = c.get("drain.slot_iters_padded")
    if live is None or not padded:
        return None
    return 100.0 * live / padded
