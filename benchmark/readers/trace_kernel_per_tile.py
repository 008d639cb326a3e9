"""Device milliseconds per kernel tile of the kernel whose trace events
match `pattern`: its summed device time in the traced slice over the
tiles it ran there.

The reduced trace keeps an event's name and times, not the arguments
the profiler stores as its stats, so the `tiles=` of the program's
`fdt.verify.land` spans cannot be summed here.  The tiles are counted
instead as the slice's kernel calls times the window's tiles a device
batch: the window deltas of the counters `tiles` and `batches` (the
verify tile's `device_tiles` and `device_batches`).  The slice lies in
the middle of the window, so its batches are the window's batches.  A
batch the host served counts a batch and no tile; a run with one is not
`correct`.

It prints its parts on a `benchmark kernel_tiles:` line, with the
slice's count of `spans` (the land spans, one a batch) beside its
kernel calls.  None with no trace, no call, or a program that does not
count its tiles (the counter is missing).
"""

import re

from benchmark.lib import tracered


def _delta(ctx, tile, name):
    before = ctx["before"].get(tile, {})
    after = ctx["after"].get(tile, {})
    if name not in after or name not in before:
        return None
    return after[name] - before[name]


def read(ctx, pattern, tiles, batches, spans):
    t = ctx.get("trace")
    if not t:
        return None
    secs, calls = tracered.kernel(t["events"], pattern)
    n_tiles, n_batches = _delta(ctx, *tiles), _delta(ctx, *batches)
    if not calls or not n_tiles or not n_batches:
        return None
    per_batch = n_tiles / n_batches
    ms = 1e3 * secs / (calls * per_batch)
    lands = sum(1 for plane, _, events in t["events"]
                if not re.search(tracered.DEVICE_PLANE, plane)
                for name, _, _ in events if re.search(spans, name))
    print(f"benchmark kernel_tiles: slice_calls={calls} slice_spans={lands} "
          f"slice_kernel_s={secs:.6f} window_batches={n_batches} "
          f"window_tiles={n_tiles} tiles_per_batch={per_batch:.4f} "
          f"ms_per_batch={1e3 * secs / calls:.5f} ms_per_tile={ms:.5f}",
          flush=True)
    return ms
