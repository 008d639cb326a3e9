"""The mean of the program's log2-bucket hists over the window: sum of
the `sum` words' growth / sum of the `count` words' growth, over the
named [tile, hist] pairs, times `scale` (0.001: a hist in us, read in
ms).  Exact where the percentile reader moves in factors of two: the
program adds every sample's value to `sum`.  None when no count moved —
and when a delta went negative (a torn read, or a tile that restarted
inside the window), never a number made from it."""


def read(ctx, hists, scale=1.0):
    total = count = 0
    for tile, name in hists:
        a = ctx["after"].get(tile, {}).get(name)
        b = ctx["before"].get(tile, {}).get(name)
        if a is None or b is None:
            continue
        d_sum, d_count = a["sum"] - b["sum"], a["count"] - b["count"]
        if d_sum < 0 or d_count < 0:
            return None
        total += d_sum
        count += d_count
    return scale * total / count if count > 0 else None
