"""Device drain: device ms per scenario of the compiled drain programs
(`jit__drain_one`), the union of their module intervals in the profile."""


def read(ctx):
    t = ctx["trace"].get("drain_device_s")
    if not t or not ctx["scenarios"]:
        return None
    return 1e3 * t / ctx["scenarios"]
