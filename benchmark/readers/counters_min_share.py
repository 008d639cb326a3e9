"""The least of the named counters' window deltas over their sum, times
`scale` (100: a share in %).  For the placement of a pool's batches over
its N devices: 100 / N is even, 0 is a device that took nothing.

args: counters = list of [tile, counter].  None when a named counter is
not there (another deployment), when nothing was counted in the window,
or when a delta went negative (a torn read, or a tile that restarted
inside the window): never a number made from that."""


def read(ctx, counters, scale=1.0):
    deltas = []
    for tile, name in counters:
        a = ctx["after"].get(tile, {}).get(name)
        b = ctx["before"].get(tile, {}).get(name)
        if a is None or b is None or a < b:
            return None
        deltas.append(a - b)
    total = sum(deltas)
    return scale * min(deltas) / total if total else None
