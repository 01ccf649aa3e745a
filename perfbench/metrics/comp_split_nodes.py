"""Tree nodes split by components per call: the program's
``comp_split_nodes`` counter on each level's ``split_components`` span
(``core/rsb.py``), the nodes whose subgraph is disconnected and is ordered
by component instead of by one Fiedler vector."""

import pb_spans


def read(run):
    return pb_spans.counter_per_call(run, "comp_split_nodes")
