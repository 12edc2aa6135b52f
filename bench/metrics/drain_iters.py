"""Device drain: `lax.while_loop` iterations per scenario, from the drain's
own `it` counter (one count per compiled group, read as `_finalize`
replays it)."""


def read(ctx):
    it = ctx["counters"].get("drain_iters")
    if it is None or not ctx["scenarios"]:
        return None
    return it / ctx["scenarios"]
