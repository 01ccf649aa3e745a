"""Share of the timed calls' wall time in which no op ran on the device,
from the profiler trace, in percent."""

import pb_trace


def read(run):
    if run.events is None:
        return None
    window = sum(c.t1 - c.t0 for c in run.calls) * 1e9
    return 100.0 * (1.0 - pb_trace.busy_ns(run.events, run.windows()) / window)
