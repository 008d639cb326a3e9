"""The median of the terminal counter's whole-second rates inside the
window: the rate's steady twin.  An episode that lasts a few seconds
(PERF.md section 6: ingress.flood's two rates) moves the whole-window
rate by its length and this median not at all, while under half of the
window's seconds are inside one."""

import statistics


def read(ctx):
    pts = [(t, c) for t, c, *_ in ctx.get("by_second", [])
           if ctx["t0_ns"] <= t <= ctx["t1_ns"] + 50_000_000]
    rates = [(c1 - c0) / ((t1 - t0) / 1e9)
             for (t0, c0), (t1, c1) in zip(pts, pts[1:]) if t1 > t0]
    return statistics.median(rates) if len(rates) >= 3 else None
