"""sum(num counters) / sum(den counters), window deltas, times `scale`.

args: num, den = lists of [tile, counter] ("*" as the tile = every tile
that has the counter; `where` = [counter, value] keeps only tiles whose
counter reads that at the window's end); den_scale multiplies the
denominator; scale the result (100 for a share in %)."""


def _terms(ctx, terms, where):
    out = []
    for tile, name in terms:
        tiles = list(ctx["after"]) if tile == "*" else [tile]
        for t in tiles:
            snap = ctx["after"].get(t, {})
            if name not in snap:
                continue
            if where and snap.get(where[0]) != where[1]:
                continue
            out.append((t, name))
    return out


def read(ctx, num, den, scale=1.0, den_scale=1.0, where=None):
    def delta(terms):
        ts = _terms(ctx, terms, where)
        if not ts:
            return None
        return sum(ctx["after"][t][n] - ctx["before"][t][n] for t, n in ts)

    a, b = delta(num), delta(den)
    if a is None or not b:
        return None
    return scale * a / (b * den_scale)
