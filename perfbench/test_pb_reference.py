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
