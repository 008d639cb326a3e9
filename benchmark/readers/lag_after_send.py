"""A percentile, in ms, of the completion lag counted from when the txn
really LEFT the benchmark's sender (`lib/sampler.after_send`: t_done -
max(t_due, sent_at), over every valid first-seen txn due in the window).
`lag_p50_ms` / `lag_p95_ms` count from the due time; the distance between
the two is the generator's share.  Open-loop cells only."""

import numpy as np


def read(ctx, q):
    lag = ctx.get("lag_after_send_ns")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(np.asarray(lag, np.float64), q)) / 1e6
