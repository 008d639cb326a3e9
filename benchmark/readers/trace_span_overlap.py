"""Of the summed time of the program's host spans whose name matches
`pattern`, the share during which ANOTHER matching span is open, times
`scale` (100: a share in %).  Spans of one thread follow one another, so
two open at once are two threads': 0 says the workers' calls queue one
behind the other (one GIL), (N - 1) / N x 100 that N ran side by side all
the time.

Events on any plane that is not a device; the profiler may write an
annotation's arguments into its name, so the match is a search
(trace_host_span).  The same span listed on two lines of the trace (same
name, start and duration) counts once.  None with no trace or no match."""

import re

from benchmark.lib import tracered


def read(ctx, pattern, scale=1.0):
    t = ctx.get("trace")
    if not t:
        return None
    spans = {(name, s, d) for plane, _, events in t["events"]
             if not re.search(tracered.DEVICE_PLANE, plane)
             for name, s, d in events if d > 0 and re.search(pattern, name)}
    if not spans:
        return None
    # sweep the edges: while k spans are open each of them has k - 1 others
    edges = sorted([(s, 1) for _, s, d in spans]
                   + [(s + d, -1) for _, s, d in spans])
    total = shared = depth = 0
    for (at, step), (nxt, _) in zip(edges, edges[1:]):
        depth += step
        total += depth * (nxt - at)
        if depth > 1:
            shared += depth * (nxt - at)
    return scale * shared / total
