"""Launches of the Lanczos restart program (``_packed_restart``) per call:
the program's ``restart_launches`` counter, one count per launch at each
level (``core/lanczos.py``)."""

import pb_spans


def read(run):
    return pb_spans.counter_per_call(run, "restart_launches")
