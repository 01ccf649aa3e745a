"""Device idle seconds per call inside the ``restarts`` annotations that
the program's spans write into the profiler trace: the host side of the
Lanczos restart loop (dispatch, the per-restart sync, the NumPy
bookkeeping) while the device waits.  Both ends are read on the trace's
own clock, with no clock offset."""

import pb_trace

SPAN = "restarts"


def read(run):
    if run.events is None:
        return None
    # One host thread runs the loop, so its annotations never overlap.
    windows = [[e.start_ns, e.end_ns] for e in run.events
               if e.name == SPAN
               and not e.plane.startswith(pb_trace.DEVICE_PREFIX)]
    if not windows:
        return None
    idle = sum(e - s for s, e in windows) - pb_trace.busy_ns(run.events,
                                                             windows)
    return idle / 1e9 / len(run.calls)
