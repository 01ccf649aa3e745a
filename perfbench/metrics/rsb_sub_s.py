"""Seconds per call relabelling each node's subgraph into its reorder
(``g.sub``): Σ of the ``sub`` spans of the RSB level loop (``core/rsb.py``)."""

import pb_spans


def read(run):
    return pb_spans.seconds_per_call(run, "sub")
