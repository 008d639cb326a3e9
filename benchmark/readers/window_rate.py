"""The terminal counter's growth between the window's two edges over the
window: ALL the work and all the time of the window (an end-to-end
rate).  Which counter is the configuration's `terminal`."""


def read(ctx):
    return ctx["rate_tps"]
