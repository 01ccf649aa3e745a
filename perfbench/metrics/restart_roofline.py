"""Share of its roofline that the Lanczos restart reaches: the least time
the chip needs for the restarts the calls made (``pb_roofline``), over
their device time in the trace, in percent."""

import pb_reference
import pb_roofline
import pb_trace

PROGRAM = "_packed_restart"
# The default pipeline's Lanczos window under multilevel warm starts
# (the RSB engine's default; the report does not carry it).
WINDOW = 20


def least_seconds_of_call(run, call) -> float:
    levels: dict = {}
    for level, _, idx in pb_reference.tree_nodes(call.raw, run.nparts):
        levels.setdefault(level, []).append(idx)
    total = 0.0
    for level, nodes in levels.items():
        recs = [r for r in call.records if r["level"] == level]
        if [r["size"] for r in recs] != [idx.size for idx in nodes]:
            raise ValueError(f"level {level}: records do not match the "
                             "bisection tree rebuilt from the raw labels")
        lanczos = [idx for idx, r in zip(nodes, recs)
                   if r["method"] == "lanczos"]
        if not lanczos:
            continue
        n = sum(idx.size for idx in lanczos)
        nnz = sum(pb_reference.subgraph(run.graph, idx).nnz for idx in lanczos)
        restarts = max(r["iterations"] for r in recs
                       if r["method"] == "lanczos")
        flops, nbytes = pb_roofline.restart_work(n, nnz, WINDOW)
        t, _ = pb_roofline.least_seconds(flops, nbytes, run.device_kind)
        total += restarts * t
    return total


def read(run):
    if run.events is None:
        return None
    device = pb_trace.program_ns(run.events, PROGRAM, run.windows()) / 1e9
    if device <= 0:
        return None
    least = sum(least_seconds_of_call(run, c) for c in run.calls)
    return 100.0 * least / device
