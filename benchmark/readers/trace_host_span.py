"""Mean milliseconds per event of the program's own host spans in the
profiler trace: events on any plane that is not a device whose name
matches `pattern`.  The profiler may write an annotation's arguments
into its name (`fdt.verify.dispatch#seq=7,lanes=85#`), so the match is a
search, not an equality.  None with no trace or no match."""

import re

from benchmark.lib import tracered


def read(ctx, pattern):
    t = ctx.get("trace")
    if not t:
        return None
    durs = [d for plane, _, events in t["events"]
            if not re.search(tracered.DEVICE_PLANE, plane)
            for name, _, d in events if re.search(pattern, name)]
    return 1e-6 * sum(durs) / len(durs) if durs else None
