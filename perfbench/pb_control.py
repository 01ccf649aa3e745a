"""Readings for the limits of ``correct``, on the chip at a cell's size.

    python3 perfbench/pb_control.py --workload box32.p16.default \\
        --sound 1,2,3,4,5,6,7,8,9,10,11,12 --control 1,2,3 --fault 1,2,3

The cell's configuration may hold a hex mesh (``mesh``) or an undirected
graph of a graph kind (``graph``, ``perfbench/graphs/<kind>.py``).  One
process reads, for the cell:

- ``sound``: for each seed, one call of the program on the cell's input
  in the element or node order of the seed's first timed call, every
  number the harness compares (``pb_harness.check_calls``), and the
  reference's cut: ``rcb_cut``, plain RCB's, where the input has
  coordinates, and ``ref_cut``, plain RSB's, for a graph;
- ``control``: for each seed, a whole run of the harness with
  :class:`Bfloat16Control` in the program's place;
- ``fault``: for each seed, a whole run of the harness with
  :class:`ScrambledSegment` in the program's place.

Each prints one JSON line per seed with the numbers and their limits.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# The benchmark's own runs use one BLAS thread (run.py): so do these.
for _var in ("OPENBLAS_NUM_THREADS", "OMP_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ.setdefault(_var, "1")

import numpy as np  # noqa: E402


def call_graph(obj):
    """The reference graph of what one call was handed, in its order: the
    dual graph of a ``HexMesh``, the adjacency of a ``Graph``."""
    import pb_reference as ref

    if hasattr(obj, "vert_gid"):
        return ref.dual_graph(obj.vert_gid)
    src = np.repeat(np.arange(obj.n), np.diff(obj.indptr))
    return ref.graph_from_edges(obj.n, src, obj.indices)


class Bfloat16Control:
    """The plain reference in the program's place for the λ₂ numbers: the
    program's labels, with the eigenvalue of every node of the bisection
    tree from the plain Lanczos of ``pb_reference.lanczos_lambda2`` in
    ``dtype`` on the node's subgraph, started from ``seed``."""

    def __init__(self, pipe, seed: int, dtype: str = "bfloat16",
                 steps: int = 300):
        self.pipe, self.seed, self.dtype, self.steps = pipe, seed, dtype, steps

    def run(self, obj, nparts: int, **kw):
        import jax.numpy as jnp

        import pb_reference as ref

        ctx = self.pipe.run(obj, nparts, **kw)
        g = call_graph(obj)
        nodes = ref.tree_nodes(np.asarray(ctx.parts_raw), nparts)
        for (_, _, idx), rec in zip(nodes, ctx.report.records):
            rec.eigenvalue = ref.lanczos_lambda2(
                ref.subgraph(g, idx), dtype=getattr(jnp, self.dtype),
                steps=self.steps, seed=self.seed)
        return ctx


class ScrambledSegment:
    """The program with one answer altered where it is made: at every
    level below the top, the Fiedler vector of the level's last node is
    shuffled before the split."""

    def __init__(self, pipe, seed: int):
        self.pipe, self.rng = pipe, np.random.default_rng(seed % 2**64)

    @contextlib.contextmanager
    def _broken(self):
        import repro.core.rsb as rsb

        solve = rsb.fiedler_from_graph_batched

        def broken(graphs, **kw):
            res = solve(graphs, **kw)
            if len(graphs) > 1:
                last = res[-1]
                res[-1] = dataclasses.replace(
                    last, vector=self.rng.permutation(np.asarray(last.vector)))
            return res

        rsb.fiedler_from_graph_batched = broken
        try:
            yield
        finally:
            rsb.fiedler_from_graph_batched = solve

    def run(self, obj, nparts: int, **kw):
        with self._broken():
            return self.pipe.run(obj, nparts, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json, on a mesh or a graph kind")
    ap.add_argument("--sound", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    root = os.getcwd()
    sys.path[:0] = [HERE, os.path.join(root, "src")]
    import pb_harness
    from repro.configs.parrsb import make_pipeline

    _, cell, config, traffic = pb_harness.resolve_cell(root, args.workload)
    pb_harness.use_cache(root)
    devices = pb_harness.check_device(cell["chips"])
    nparts = int(traffic["nparts"])
    pipe = make_pipeline(traffic["preset"], **traffic.get("pipeline", {}))
    inp = pb_harness.base_input(config)

    def seeds(text):
        return [int(s) for s in text.split(",") if s]

    calls = []
    for seed in seeds(args.sound):
        perm = pb_harness.seed_rng(seed, 1).permutation(inp.n)
        calls.append(pb_harness.timed_call(pipe, inp.call(perm), nparts,
                                           perm))
    if calls:
        checks, _, _, info = pb_harness.check_calls(calls, inp, nparts,
                                                    config)
        cuts = {k: info[k] for k in ("ref_cut", "rcb_cut") if k in info}
        for seed, c, row in zip(seeds(args.sound), calls, info["rows"]):
            print(json.dumps({"workload": args.workload, "kind": "sound",
                              "seed": seed, "seconds": c.seconds,
                              "device": devices[0].device_kind,
                              "numbers": row, **cuts,
                              "limits": {k: v[1] for k, v in checks.items()}}),
                  flush=True)
    with open(os.devnull, "w") as quiet:
        for kind, make in (("control", Bfloat16Control),
                           ("fault", ScrambledSegment)):
            for seed in seeds(getattr(args, kind)):
                r = pb_harness.run_cell(
                    root, args.workload, seed, 0.01, False,
                    t_start=time.perf_counter(), pipeline=make(pipe, seed),
                    out=quiet)
                print(json.dumps({"workload": args.workload, "kind": kind,
                                  "seed": seed, "correct": r["correct"],
                                  "failed": r["failed"],
                                  "attempted": r["attempted"],
                                  "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
