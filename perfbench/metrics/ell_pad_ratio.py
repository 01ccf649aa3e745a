"""Padded ELL slots per stored Laplacian entry over a call's restart
launches: the program's ``ell_slots`` over its ``ell_nnz`` counter, both
on the ``pack`` span (``core/fiedler.py``) and each summed over the
launches that gather over that packing.  1 is an ELL without padding;
hubs make every row as wide as the widest."""

import pb_spans


def read(run):
    slots = pb_spans.counter_per_call(run, "ell_slots")
    nnz = pb_spans.counter_per_call(run, "ell_nnz")
    if slots is None or not nnz:
        return None
    return slots / nnz
