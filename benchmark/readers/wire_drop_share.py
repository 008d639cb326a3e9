"""Share of the window's datagrams lost at the wire edge, in %: kernel
socket drops (/proc/net/udp, taken by the harness at both edges) plus
the named tile counters, over datagrams sent in the window."""


def read(ctx, counters):
    sent = ctx["sent_after"] - ctx["sent_before"]
    if sent <= 0:
        return None
    lost = ctx["kdrops_after"] - ctx["kdrops_before"]
    for tile, name in counters:
        lost += ctx["after"][tile][name] - ctx["before"][tile][name]
    return 100.0 * lost / sent
