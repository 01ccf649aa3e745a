"""The default pipeline on small R-MAT graphs of the products-skew recipe,
held to the checks the harness holds the cell to, and the readers of the
counters that the cell reports.

The graphs are those of ``configs/products-skew.json`` at scales 8 and 9
(245 and 477 nodes), in 4 and 16 parts and several node orders.  The cut
limit is the configuration's; the λ₂ limits are box32's, as the
configuration's are."""

import json
import os

import numpy as np
import pytest

import pb_graph
import pb_harness
import pb_reference as ref
from repro.obs import Span

HERE = os.path.dirname(os.path.abspath(__file__))


def _config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


PRODUCTS = _config("products-skew")


@pytest.fixture(scope="module")
def pipe():
    from repro.configs.parrsb import make_pipeline

    return make_pipeline("default")


@pytest.fixture(scope="module", params=[8, 9])
def graph(request):
    inp = pb_graph.build({**PRODUCTS["graph"], "scale": request.param})
    g = inp.reference_graph()
    return inp, g, ref.NodeLambda2(g, lambda sub: ref.fiedler_pair(sub)[0])


@pytest.mark.parametrize("nparts", [4, 16])
@pytest.mark.parametrize("order", [2**31 + 1, 2**31 + 2, 2**31 + 3])
def test_the_default_pipeline_meets_the_cells_checks(pipe, graph, nparts,
                                                     order):
    inp, g, lam2 = graph
    perm = pb_harness.seed_rng(order, 1).permutation(inp.n)
    call = pb_harness.timed_call(pipe, inp.call(perm), nparts, perm)
    r = ref.check_partition(g, call.labels, inp.weights, nparts,
                            PRODUCTS["checks"]["balance_tol"])
    assert r["out_of_range"] == r["empty_parts"] == 0
    assert r["disconnected_parts"] == 0
    assert r["balance"] <= PRODUCTS["checks"]["balance_tol"]
    ref_cut = ref.edge_cut(g, ref.rsb_labels(g, inp.weights, nparts))
    assert r["cut"] / ref_cut <= PRODUCTS["checks"]["cut_vs_ref"]
    box = _config("box32")["checks"]
    top, deep = pb_harness.lambda2_errors(call, lam2, nparts)
    assert top <= box["lam2_rel_err"] and deep <= box["lam2_deep_rel_err"]


def test_a_disconnected_node_is_ordered_as_the_reference_orders_it():
    """Two R-MAT graphs side by side, small enough for dense solves, guard
    off so the root stays whole: the root reports λ₂ = 0, and its first
    part is the reference's spectral order cut at the weighted point,
    which falls inside the first component."""
    import scipy.sparse as sp

    from repro.configs.parrsb import make_pipeline
    from repro.mesh.graphs import Graph

    a, b = (pb_graph.build({**PRODUCTS["graph"], "scale": 7,
                            "graph_seed": s}) for s in (1, 2))
    adj = sp.block_diag([a.adj, b.adj]).tocsr()
    n, nparts = adj.shape[0], 3
    obj = Graph(n=n, indptr=adj.indptr.astype(np.int64),
                indices=adj.indices.astype(np.int64),
                weights=adj.data.astype(np.float64))
    ctx = make_pipeline("raw", guard=False).run(obj, nparts)
    top = ctx.report.records[0]
    assert top.eigenvalue == 0.0 and top.method == "components"
    k = int(np.ceil(n / nparts))
    assert k < a.n
    order = ref.spectral_order(ref.DualGraph(adj=adj))
    got = np.flatnonzero(np.asarray(ctx.parts_raw) == 0)
    assert np.array_equal(np.sort(order[:k]), got)


def _tree(levels, repair, packs):
    """A call's span tree: per level, a ``split_components`` span with its
    counter; ``repair`` spans with theirs; ``pack`` spans with slots and
    entries."""
    children = [{"name": f"level:{i}", "seconds": 1.0, "children": [
        {"name": "split_components", "seconds": 0.1,
         "counters": {"comp_split_nodes": c}},
        {"name": "solve", "seconds": 0.5, "children": [
            {"name": "pack", "seconds": 0.1,
             "counters": {"ell_slots": s, "ell_nnz": z}}]}]}
        for i, (c, (s, z)) in enumerate(zip(levels, packs))]
    children += [{"name": "repair", "seconds": 0.1,
                  "counters": {"repair_moved": m}} for m in repair]
    return Span.from_dict({"name": "partition", "seconds": 9.0,
                           "children": children})


def _run(*trees):
    calls = [pb_harness.Call(t0=0.0, t1=1.0, labels=None, raw=None, stages=[],
                             levels=[], records=[], span=t) for t in trees]
    return pb_harness.Run(calls=calls, nparts=16, graph=None,
                          device_kind="TPU v5 lite")


@pytest.mark.parametrize("metric, want", [
    ("comp_split_nodes", (1 + 2 + 0) / 2),
    ("repair_moved", (3 + 4 + 5) / 2),
    ("ell_pad_ratio", (100 + 50 + 10) / (10 + 5 + 5)),
])
def test_the_products_readers_sum_per_call(metric, want):
    run = _run(_tree([1, 2], [3, 4], [(100, 10), (50, 5)]),
               _tree([0], [5], [(10, 5)]))
    assert pb_harness.load_reader(metric).read(run) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["comp_split_nodes", "repair_moved",
                                    "ell_pad_ratio"])
def test_the_products_readers_read_nothing_where_nothing_counts(metric):
    """A program without these counters (the parent's) reads nothing."""
    bare = Span.from_dict({"name": "partition", "seconds": 1.0})
    assert pb_harness.load_reader(metric).read(_run(bare, bare)) is None
    assert pb_harness.load_reader(metric).read(_run(bare, None)) is None


def test_the_products_readers_on_a_recorded_call(pipe):
    """The counters of one real call of the default pipeline on the
    scale-8 graph: no component splits, slots at least the entries."""
    inp = pb_graph.build({**PRODUCTS["graph"], "scale": 8})
    perm = pb_harness.seed_rng(2**31 + 5, 1).permutation(inp.n)
    call = pb_harness.timed_call(pipe, inp.call(perm), 16, perm)
    run = pb_harness.Run(calls=[call], nparts=16, graph=None,
                         device_kind="cpu")
    assert pb_harness.load_reader("comp_split_nodes").read(run) == 0
    assert pb_harness.load_reader("repair_moved").read(run) >= 0
    assert pb_harness.load_reader("ell_pad_ratio").read(run) >= 1
