"""Seconds per call assembling the dual graph: Σ of the ``dual_graph``
span (inside ``guard:validate``, ``core/pipeline.py``)."""

import pb_spans


def read(run):
    return pb_spans.seconds_per_call(run, "dual_graph")
