"""Seconds per call packing each level's subproblems for the device: Σ of
the ``pack`` spans (layout, float64 ELL operator, start vectors and their
host-to-device copies, ``core/fiedler.py``)."""

import pb_spans


def read(run):
    return pb_spans.seconds_per_call(run, "pack")
