"""Mean device milliseconds per call of the kernel whose trace events
match `pattern`: summed device duration / calls."""

from benchmark.lib import tracered


def read(ctx, pattern):
    t = ctx.get("trace")
    if not t:
        return None
    secs, calls = tracered.kernel(t["events"], pattern)
    return 1e3 * secs / calls if calls else None
