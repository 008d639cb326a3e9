"""Growth of the named [tile, counter] pairs over the window, a second of
the window (`t1_ns` - `t0_ns`: the window's nominal edges — the two
snapshots are taken within a turn of the harness's loop of them), times
`scale`.  For counters in ns, `scale` 1e-7 reads the share of the window
in %.  None when a tile lacks the counter (a program from before it) or a
delta went negative (a torn read, a restart)."""


def read(ctx, counters, scale=1.0):
    span_ns = ctx["t1_ns"] - ctx["t0_ns"]
    if span_ns <= 0:
        return None
    total = 0
    for tile, name in counters:
        a = ctx["after"].get(tile, {}).get(name)
        b = ctx["before"].get(tile, {}).get(name)
        if a is None or b is None or a < b:
            return None
        total += a - b
    return scale * total / (span_ns / 1e9)
