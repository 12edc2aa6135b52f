"""Device: percent of the traced window in which no operation ran on the
device, 100 * (1 - busy / window), from the profile."""


def read(ctx):
    busy, win = ctx["trace"].get("busy_s"), ctx["trace"].get("window_s")
    if not busy or not win:
        return None
    return 100.0 * (1.0 - busy / win)
