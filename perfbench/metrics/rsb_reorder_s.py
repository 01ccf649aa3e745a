"""Seconds per call in the per-level geometric reorder of the RSB level
loop: Σ of the ``reorder`` spans (``rcb_order``/``rib_order`` of every node,
``core/rsb.py``)."""

import pb_spans


def read(run):
    return pb_spans.seconds_per_call(run, "reorder")
