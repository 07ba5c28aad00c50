"""collective_exposed_ms: per step, milliseconds in which a collective
operation runs on a device and no compute operation does, averaged over
the cell's devices (profiler trace).  Nothing to read on one chip."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or ctx["chips"] < 2 or not ctx["steps"]:
        return None
    if tr.collective_s <= 0:
        return None
    return 1000.0 * tr.collective_exposed_s / ctx["steps"]
