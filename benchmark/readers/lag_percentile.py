"""A percentile, in ms, of the completion lag (lib/sampler.py) over
EVERY valid first-seen txn due in the window.  Open-loop cells only: a
closed loop has no due times, and the reader then returns nothing."""

import numpy as np


def read(ctx, q):
    lag = ctx.get("lag_ns")
    if lag is None or not len(lag):
        return None
    return float(np.percentile(np.asarray(lag, np.float64), q)) / 1e6
