"""How late the benchmark's own open-loop sender ran: a percentile of
(actual send time - due time) over every send of the window, in us.  A
starved generator must not be read as a fast server."""

import numpy as np


def read(ctx, q):
    late = ctx.get("sender_late_ns")
    if late is None or not len(late):
        return None
    return float(np.percentile(late, q)) / 1e3
