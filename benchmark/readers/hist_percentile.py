"""A percentile of the program's log2-bucket hists, window delta, merged
over the named [tile, hist] pairs.  Bucket b holds values in
[2^b, 2^(b+1)) (bucket 0: [0, 2)); the estimate interpolates linearly
inside the bucket, so it moves in factors of two at worst — fine for a
layer, never for an end-to-end tail.  (The arithmetic is the
benchmark's own copy of disco/metrics.hist_percentile.)"""


def read(ctx, hists, q):
    buckets: list = []
    for tile, name in hists:
        a = ctx["after"].get(tile, {}).get(name)
        b = ctx["before"].get(tile, {}).get(name)
        if a is None or b is None:
            continue
        d = [x - y for x, y in zip(a["buckets"], b["buckets"])]
        if len(d) > len(buckets):
            buckets += [0] * (len(d) - len(buckets))
        for i, n in enumerate(d):
            buckets[i] += max(n, 0)  # a torn read can go negative
    mass = sum(buckets)
    if mass <= 0:
        return None
    rank, cum = q / 100.0 * mass, 0
    for b, n in enumerate(buckets):
        if n and cum + n >= rank:
            lo = 0.0 if b == 0 else float(1 << b)
            hi = float(1 << (b + 1))
            return lo + (hi - lo) * max(rank - cum, 0.0) / n
        cum += n
    return None
