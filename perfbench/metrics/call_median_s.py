"""The median of the window's call times: one call's wall time to host
labels without the calls that other load on the host drew out."""

import statistics


def read(run):
    return statistics.median(c.seconds for c in run.calls)
