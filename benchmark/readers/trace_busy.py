"""Device idle share from the profiler trace of the process that holds
the chip: 100 * (1 - union of device-op intervals / traced window)."""


def read(ctx):
    t = ctx.get("trace")
    if not t or t["busy_s"] is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
