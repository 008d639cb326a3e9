"""The share of the window that the closed loop's own thread spent in
sending, in %.  Near 100 the benchmark's sender, not the system, bounds
the cell's rate; None where the cell has no closed loop."""


def read(ctx):
    busy = ctx.get("send_busy_share")
    return None if busy is None else 100.0 * busy
