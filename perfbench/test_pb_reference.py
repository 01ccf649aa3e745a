"""The plain checker and the λ₂ reference on a small box."""

import jax.numpy as jnp
import numpy as np
import pytest

import pb_mesh
import pb_reference as ref

NPARTS = 4
TOL = 0.05


@pytest.fixture(scope="module")
def box():
    vert, coords = pb_mesh.grid(12, 12, 12)
    return ref.dual_graph(vert), coords, np.ones(len(coords))


def test_dual_graph_weights_count_shared_vertices():
    vert, _ = pb_mesh.grid(2, 2, 1)
    g = ref.dual_graph(vert)
    # Four elements in a square: each shares a face (4 vertices) with two
    # and an edge (2 vertices) with one.
    assert g.n == 4 and g.nnz == 12
    assert sorted(g.adj[0].data) == [2.0, 4.0, 4.0]
    labels = np.array([0, 0, 1, 1])            # one cut through the faces
    assert ref.edge_cut(g, labels) == 4 + 4 + 2 + 2


def _sound(box):
    g, coords, w = box
    return ref.rcb_labels(coords, w, NPARTS)


def test_a_correct_partition_passes(box):
    g, _, w = box
    r = ref.check_partition(g, _sound(box), w, NPARTS, TOL)
    assert (r["out_of_range"], r["empty_parts"], r["disconnected_parts"]) \
        == (0, 0, 0)
    assert r["balance"] <= TOL
    assert r["cut"] == ref.edge_cut(g, _sound(box))


def test_a_disconnected_part_fails(box):
    g, coords, w = box
    labels = _sound(box)
    a = int(np.flatnonzero(labels == 0)[0])
    b = int(np.flatnonzero(labels == 3)[-1])
    labels[a], labels[b] = 3, 0                # swap two far corners
    r = ref.check_partition(g, labels, w, NPARTS, TOL)
    assert r["disconnected_parts"] == 2
    assert r["balance"] <= TOL


def test_a_part_out_of_the_corridor_fails(box):
    g, coords, w = box
    labels = _sound(box)
    idx = np.flatnonzero(labels == 1)
    labels[idx[:40]] = 0                       # 40 of 432 elements move
    r = ref.check_partition(g, labels, w, NPARTS, TOL)
    assert r["balance"] > TOL


def test_an_empty_part_fails(box):
    g, _, w = box
    labels = _sound(box)
    labels[labels == 3] = 2
    assert ref.check_partition(g, labels, w, NPARTS, TOL)["empty_parts"] == 1


def test_a_label_out_of_range_fails(box):
    g, _, w = box
    labels = _sound(box)
    labels[7] = NPARTS
    assert ref.check_partition(g, labels, w, NPARTS, TOL)["out_of_range"] == 1
    labels[7] = -1
    assert ref.check_partition(g, labels, w, NPARTS, TOL)["out_of_range"] == 1


def test_lambda2_reference_against_a_dense_solve():
    vert, _ = pb_mesh.grid(5, 4, 3)
    g = ref.dual_graph(vert)
    dense = np.linalg.eigvalsh(g.laplacian().toarray())
    assert ref.lambda2_reference(g) == pytest.approx(dense[1], rel=1e-10)


def test_tree_nodes_follow_the_part_ranges():
    raw = np.array([0, 1, 2, 2, 0, 1, 2])
    nodes = ref.tree_nodes(raw, 3)
    # Parts [0, 3) split into [0, 1) and [1, 3); only the latter is solved.
    assert [(lv, p, idx.tolist()) for lv, p, idx in nodes] == [
        (0, 3, list(range(7))), (1, 2, [1, 2, 3, 5, 6])]


def test_tree_nodes_skip_one_part_ranges():
    # Parts [0, 5) split into [0, 2) and [2, 5); at level 2 only [3, 5)
    # is bisected, since [0, 1), [1, 2) and [2, 3) hold one part each.
    raw = np.arange(5).repeat(2)
    nodes = ref.tree_nodes(raw, 5)
    assert [(lv, p) for lv, p, _ in nodes] == [(0, 5), (1, 2), (1, 3),
                                                (2, 2)]
    assert len(ref.tree_nodes(np.arange(16), 16)) == 1 + 2 + 4 + 8
    assert ref.tree_nodes(np.zeros(4, int), 1) == []


def test_node_lambda2_matches_a_dense_solve_and_keeps_it(box):
    g = box[0]
    idx = np.flatnonzero(box[1][:, 0] < 0.5)
    lam2 = ref.NodeLambda2(g)
    dense = np.linalg.eigvalsh(ref.subgraph(g, idx).laplacian().toarray())
    assert lam2(idx)[0] == pytest.approx(dense[1], rel=1e-9)
    assert lam2.rel_err(dense[1] * 1.01, idx[::-1]) == pytest.approx(0.01)
    assert len(lam2._seen) == 1


def test_a_disconnected_node_is_measured_against_its_degree(box):
    g, coords, _ = box
    idx = np.flatnonzero((coords[:, 0] < 0.2) | (coords[:, 0] > 0.8))
    lam, scale = ref.NodeLambda2(g)(idx)
    assert lam == 0.0 and scale > 1.0


def test_a_bfloat16_top_level_eigenvalue_fails(box):
    """The control at a size a test run holds: on a 12^3 cube the plain
    Lanczos in bfloat16 reads far above the cube configuration's λ₂ limit,
    and in float32 far below it."""
    import json
    import os

    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "configs", "box32.json")) as f:
        limit = json.load(f)["checks"]["lam2_rel_err"]
    g = box[0]
    lam = ref.lambda2_reference(g)
    for seed in (1, 2, 3):
        bf16 = ref.lanczos_lambda2(g, dtype=jnp.bfloat16, steps=120,
                                   seed=seed)
        f32 = ref.lanczos_lambda2(g, dtype=jnp.float32, steps=120, seed=seed)
        assert abs(bf16 - lam) / lam > 3 * limit
        assert abs(f32 - lam) / lam < limit / 3


# -- graph inputs ------------------------------------------------------------

def test_graph_from_edges_is_symmetric_with_unit_edges():
    # (0, 1) twice and in both directions, (2, 2) a self-loop.
    g = ref.graph_from_edges(4, np.array([0, 1, 0, 2, 2, 3]),
                             np.array([1, 0, 1, 2, 3, 1]))
    a = g.adj.toarray()
    assert (a == a.T).all() and set(np.unique(a)) == {0.0, 1.0}
    assert np.trace(a) == 0 and g.nnz == 6
    assert ref.edge_cut(g, np.array([0, 0, 1, 1])) == 1.0


def _dense_rsb(g, weights, nparts):
    """Recursive bisection by the dense float64 Fiedler vector."""
    labels = np.zeros(g.n, dtype=np.int64)

    def rec(idx, lo, hi):
        p = hi - lo
        if p <= 1:
            labels[idx] = lo
            return
        lap = ref.subgraph(g, idx).laplacian().toarray()
        order = idx[np.argsort(np.linalg.eigh(lap)[1][:, 1], kind="stable")]
        cw = np.cumsum(weights[order])
        k = int(np.searchsorted(cw, cw[-1] * (p // 2) / p)) + 1
        rec(order[:k], lo, lo + p // 2)
        rec(order[k:], lo + p // 2, hi)

    rec(np.arange(g.n), 0, nparts)
    return labels


def _parts(labels):
    return {frozenset(np.flatnonzero(labels == p)) for p in set(labels)}


@pytest.mark.parametrize("nparts", [2, 4, 8])
def test_rsb_labels_against_a_dense_fiedler_split(nparts):
    """On a 32 x 20 lattice (640 nodes, above the dense cutoff, so that the
    top node takes the sparse solve) every split falls between whole
    columns or rows of tied Fiedler values."""
    import pb_graph

    g = pb_graph.build({"kind": "grid", "dims": [32, 20], "coords": False})
    g, w = g.reference_graph(), g.weights
    got = ref.rsb_labels(g, w, nparts)
    assert _parts(got) == _parts(_dense_rsb(g, w, nparts))
    assert ref.edge_cut(g, got) == {2: 20, 4: 20 + 2 * 16,
                                     8: 20 + 2 * 16 + 4 * 10}[nparts]


def test_fiedler_pair_against_a_dense_solve(monkeypatch):
    """The sparse solve, and the ARPACK solve that takes over where LOBPCG
    leaves a large residual, against ``eigh`` on a graph with hubs."""
    import pb_graph

    g = pb_graph.build({"kind": "rmat", "scale": 10, "edge_factor": 8,
                        "abcd": [0.57, 0.19, 0.19, 0.05], "graph_seed": 3})
    g = g.reference_graph()
    assert g.n > ref.DENSE_N
    vals, vecs = np.linalg.eigh(g.laplacian().toarray())
    lam, v = ref.fiedler_pair(g)
    assert lam == pytest.approx(vals[1], rel=1e-9)
    assert abs(v @ vecs[:, 1]) == pytest.approx(1.0, abs=1e-6)
    assert v[np.argmax(np.abs(v))] > 0
    monkeypatch.setattr(ref.sla, "lobpcg",
                        lambda A, X, **kw: (np.ones(2), X))
    lam2, v2 = ref.fiedler_pair(g)
    assert lam2 == pytest.approx(vals[1], rel=1e-9)
    assert v2 @ v == pytest.approx(1.0, abs=1e-6)


def test_a_disconnected_node_is_ordered_by_component_then_fiedler():
    # Component 0: a path 0-2-4-6; component 1: the path 1-3-5.
    g = ref.graph_from_edges(7, np.array([0, 2, 4, 1, 3]),
                             np.array([2, 4, 6, 3, 5]))
    order = ref.spectral_order(g)
    assert set(order[:4]) == {0, 2, 4, 6} and set(order[4:]) == {1, 3, 5}
    assert order[:4].tolist() in ([0, 2, 4, 6], [6, 4, 2, 0])
    assert order[4:].tolist() in ([1, 3, 5], [5, 3, 1])
