"""Graph inputs: the R-MAT kind, the checks :mod:`pb_graph` makes of every
kind, and the node relabel that each call gets."""

import types

import numpy as np
import pytest
import scipy.sparse.csgraph as csgraph

import pb_graph
import pb_harness

RMAT12 = {"kind": "rmat", "scale": 12, "edge_factor": 25.26,
          "abcd": [0.57, 0.19, 0.19, 0.05], "graph_seed": 2**31 + 1}


@pytest.fixture(scope="module")
def rmat12():
    return pb_graph.build(RMAT12)


def test_rmat_is_the_same_for_a_graph_seed_and_not_for_another():
    kind = pb_graph.load_kind("rmat")
    a, b = kind.build(RMAT12), kind.build(RMAT12)
    c = kind.build({**RMAT12, "graph_seed": 2**31 + 2})
    assert a[0] == b[0] and all(np.array_equal(x, y)
                                for x, y in zip(a[1:4], b[1:4]))
    assert a[0] != c[0] or not np.array_equal(a[1], c[1])


def test_rmat_is_symmetric_loop_free_connected_and_skewed(rmat12):
    n, src, dst, w, coords = pb_graph.load_kind("rmat").build(RMAT12)
    assert coords is None and np.array_equal(w, np.ones(n))
    assert not (src == dst).any()
    adj = rmat12.adj
    assert rmat12.n == n and (adj != adj.T).nnz == 0
    assert adj.diagonal().sum() == 0 and set(adj.data) == {1.0}
    assert csgraph.connected_components(adj, directed=False)[0] == 1
    deg = np.diff(adj.indptr)
    assert deg.max() >= 20 * np.median(deg)
    # The largest component of 4,096 vertices, at about the edge factor.
    assert 0.5 * 4096 < n < 4096 and adj.nnz / 2 > 10 * n


def test_build_symmetrises_and_drops_duplicates_and_loops(monkeypatch):
    kind = types.SimpleNamespace(build=lambda spec: (
        4, np.array([0, 1, 1, 2, 2, 3]), np.array([1, 0, 2, 2, 3, 0]),
        np.ones(4), None))
    monkeypatch.setattr(pb_graph, "load_kind", lambda name: kind)
    g = pb_graph.build({"kind": "fake"})
    assert g.adj.toarray().tolist() == [[0, 1, 0, 1], [1, 0, 1, 0],
                                        [0, 1, 0, 1], [1, 0, 1, 0]]


def test_a_disconnected_graph_is_refused(monkeypatch):
    kind = types.SimpleNamespace(build=lambda spec: (
        4, np.array([0, 2]), np.array([1, 3]), np.ones(4), None))
    monkeypatch.setattr(pb_graph, "load_kind", lambda name: kind)
    with pytest.raises(pb_harness.SetupError, match="2 connected"):
        pb_graph.build({"kind": "fake"})


def test_a_call_gets_the_graph_in_its_node_order():
    g = pb_graph.build({"kind": "grid", "dims": [5, 4], "coords": True})
    perm = np.random.default_rng(2**31 + 5).permutation(g.n)
    prog, kw = g.call(perm)
    assert prog.n == g.n and np.array_equal(kw["weights"], g.weights[perm])
    assert np.array_equal(kw["coords"], g.coords[perm])
    assert prog.indptr.dtype == prog.indices.dtype == np.int64
    for i in range(g.n):
        row = prog.indices[prog.indptr[i]:prog.indptr[i + 1]]
        assert np.all(np.diff(row) > 0)
        base = g.adj.indices[g.adj.indptr[perm[i]]:g.adj.indptr[perm[i] + 1]]
        assert sorted(perm[row]) == sorted(base)
    assert np.array_equal(prog.weights, np.ones(prog.indices.size))
    assert "coords" not in pb_graph.build(
        {"kind": "grid", "dims": [5, 4], "coords": False}).call(perm)[1]
