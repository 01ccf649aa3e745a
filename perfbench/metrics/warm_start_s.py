"""Seconds per call in the host's work ahead of each batched Fiedler
solve: Σ of the ``warm_start`` spans (multilevel warm starts and the dense
solves of small nodes, ``core/fiedler.py``)."""

import pb_spans


def read(run):
    return pb_spans.seconds_per_call(run, "warm_start")
