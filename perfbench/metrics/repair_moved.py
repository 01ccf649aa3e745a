"""Node weight that the repair moved per call: the program's
``repair_moved`` counter on its ``repair`` spans (``core/refine.py``), the
first post stage's and the one that closes the FM refine."""

import pb_spans


def read(run):
    return pb_spans.counter_per_call(run, "repair_moved")
