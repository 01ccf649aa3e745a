"""Level-synchronous batched RSB engine: parity with the recursive engine
(balance at every level, cut quality, batched-entry-point equivalence)."""

import numpy as np
import pytest
from test_connected_split import _rmat_core
from test_rcb_segmented import _reference_segments

from repro.configs.parrsb import make_pipeline
from repro.core import (
    fiedler_from_graph,
    fiedler_from_graph_batched,
    fiedler_from_mesh,
    fiedler_from_mesh_batched,
    fiedler_oracle_np,
    partition,
    partition_metrics,
    rsb_partition_graph,
    rsb_partition_mesh,
)
from repro.core.rsb import _node_seed
from repro.mesh import (
    box_mesh,
    dual_graph,
    extract_subgraphs,
    grid_graph_2d,
    pebble_mesh,
)
from repro.mesh.graphs import connected_labels


@pytest.fixture(scope="module")
def box():
    m = box_mesh(8, 8, 4)
    return m, dual_graph(m)


@pytest.fixture(scope="module")
def pebble():
    m = pebble_mesh(10, 10, 10, n_pebbles=4, warp=0.1, seed=2)
    return m, dual_graph(m)


def _ancestor_balance_ok(parts, nparts):
    """Eq. 2.6 at EVERY level: for power-of-two nparts, the level-l ancestor
    of part p is p >> (k - l); each level's groups must be within one
    element (unit weights)."""
    k = int(np.log2(nparts))
    for level in range(k + 1):
        anc = parts >> (k - level)
        counts = np.bincount(anc, minlength=1 << level)
        if counts.max() - counts.min() > 1:
            return False
    return True


@pytest.mark.parametrize("engine", ["batched", "recursive"])
def test_balance_every_level(box, engine):
    m, _ = box
    for nparts in (4, 8, 16):
        parts, _ = rsb_partition_mesh(
            m, nparts, tol=1e-2, max_restarts=10, engine=engine
        )
        assert _ancestor_balance_ok(parts, nparts), (engine, nparts)
    # non-power-of-two still balances overall
    parts, _ = rsb_partition_mesh(m, 3, tol=1e-2, max_restarts=10, engine=engine)
    counts = np.bincount(parts, minlength=3)
    assert counts.max() - counts.min() <= 1


def test_engine_cut_parity_box(box):
    m, g = box
    pb, rb = rsb_partition_mesh(m, 8, tol=1e-3, engine="batched")
    pr, rr = rsb_partition_mesh(m, 8, tol=1e-3, engine="recursive")
    cb = partition_metrics(g, pb, 8).edge_cut
    cr = partition_metrics(g, pr, 8).edge_cut
    assert cb <= 1.05 * cr and cr <= 1.05 * cb
    assert rb.engine == "batched" and rr.engine == "recursive"


def test_engine_cut_parity_pebble(pebble):
    m, g = pebble
    pb, _ = rsb_partition_mesh(m, 8, tol=1e-3, engine="batched")
    pr, _ = rsb_partition_mesh(m, 8, tol=1e-3, engine="recursive")
    cb = partition_metrics(g, pb, 8).edge_cut
    cr = partition_metrics(g, pr, 8).edge_cut
    assert cb <= 1.05 * cr and cr <= 1.05 * cb


def test_engine_cut_parity_graph(pebble):
    m, g = pebble
    pb, _ = rsb_partition_graph(g, 8, coords=m.coords, tol=1e-3,
                                engine="batched")
    pr, _ = rsb_partition_graph(g, 8, coords=m.coords, tol=1e-3,
                                engine="recursive")
    cb = partition_metrics(g, pb, 8).edge_cut
    cr = partition_metrics(g, pr, 8).edge_cut
    assert cb <= 1.05 * cr and cr <= 1.05 * cb


def test_batched_graph_entry_matches_unbatched_on_singleton():
    g = grid_graph_2d(20, 20)  # 400 nodes: above the dense cutoff
    r1 = fiedler_from_graph(g, method="lanczos", seed=7, tol=1e-4)
    rb = fiedler_from_graph_batched([g], seeds=[7], tol=1e-4)[0]
    assert rb.eigenvalue == pytest.approx(r1.eigenvalue, rel=1e-3)
    cos = abs(np.dot(r1.vector, rb.vector)) / (
        np.linalg.norm(r1.vector) * np.linalg.norm(rb.vector)
    )
    assert cos > 0.999
    assert rb.iterations == r1.iterations


def test_batched_mesh_entry_matches_unbatched_on_singleton():
    # 8×6×4: all axes distinct → simple λ₂.  A square cross-section (8×8×4)
    # has an exactly degenerate λ₂ eigenspace whose orientation inside the
    # Ritz problem is set by fp noise — the two entry points then return
    # different (both valid) members and a vector comparison is
    # meaningless (paper §9).
    m = box_mesh(8, 6, 4)
    r1 = fiedler_from_mesh(m.vert_gid, method="lanczos", seed=3, tol=1e-3)
    rb = fiedler_from_mesh_batched([m.vert_gid], seeds=[3], tol=1e-3)[0]
    assert rb.eigenvalue == pytest.approx(r1.eigenvalue, rel=1e-3)
    cos = abs(np.dot(r1.vector, rb.vector)) / (
        np.linalg.norm(r1.vector) * np.linalg.norm(rb.vector)
    )
    assert cos > 0.999


def test_batched_entry_multiproblem_matches_oracle():
    """A heterogeneous batch: every packed subproblem must match its own
    dense eigenpair (no cross-problem coupling through the packing)."""
    graphs = [grid_graph_2d(20, 20), grid_graph_2d(16, 25),
              grid_graph_2d(24, 14)]
    results = fiedler_from_graph_batched(graphs, tol=1e-4, max_restarts=80)
    for g, r in zip(graphs, results):
        lam, _ = fiedler_oracle_np(g)
        assert r.eigenvalue == pytest.approx(lam, rel=2e-2, abs=1e-4)


def test_batched_inverse_entry_matches_oracle():
    g = grid_graph_2d(20, 20)
    r = fiedler_from_graph_batched([g], method="inverse", tol=1e-4)[0]
    lam, _ = fiedler_oracle_np(g)
    assert r.eigenvalue == pytest.approx(lam, rel=2e-2, abs=1e-4)
    assert r.method == "inverse"


@pytest.mark.parametrize("dims", [(16, 25), (14, 15)])
def test_inverse_gram_breakdown_regression(dims):
    """Regression: near-duplicate projection-window iterates made the fp32
    Gram singular (the old absolute 1e-12 ridge is below fp32 epsilon) and
    NaN vectors were reported as converged — in BOTH inverse paths.

    multilevel=False pins the original cold-noise-start scenario the ridge
    regression was observed under; the multilevel path is covered by
    test_multilevel.py (near-degenerate pairs converge to an eigenvector
    of the low cluster, not necessarily y₂ — paper §9)."""
    g = grid_graph_2d(*dims)
    lam, _ = fiedler_oracle_np(g)
    rb = fiedler_from_graph_batched([g], method="inverse", tol=1e-4,
                                    multilevel=False)[0]
    ru = fiedler_from_graph(g, method="inverse", tol=1e-4, multilevel=False)
    for r in (rb, ru):
        assert np.isfinite(r.vector).all()
        # loose eigenvalue check: the guarded early stop may accept a
        # slightly coarser iterate; the point is finite-and-sane, not tight
        assert r.eigenvalue == pytest.approx(lam, rel=5e-2, abs=1e-4)


def test_batched_dense_tail_matches_unbatched():
    g = grid_graph_2d(8, 8)  # below the dense cutoff
    r1 = fiedler_from_graph(g, tol=1e-4)
    rb = fiedler_from_graph_batched([g], tol=1e-4)[0]
    assert rb.method == "dense"
    np.testing.assert_allclose(rb.vector, r1.vector)


def test_extract_subgraphs_matches_sub(pebble):
    _, g = pebble
    rng = np.random.default_rng(0)
    perm = rng.permutation(g.n)
    lo, hi = perm[: g.n // 2], perm[g.n // 2:]
    g_lo, g_hi = extract_subgraphs(g, [lo, hi])
    for got, idx in ((g_lo, lo), (g_hi, hi)):
        ref = g.sub(idx)
        assert got.n == ref.n
        np.testing.assert_array_equal(got.indptr, ref.indptr)
        np.testing.assert_array_equal(got.indices, ref.indices)
        np.testing.assert_allclose(got.weights, ref.weights)


def test_level_records(box):
    m, _ = box
    _, rep = rsb_partition_mesh(m, 8, tol=1e-3, engine="batched")
    assert rep.levels, "batched engine must emit per-level records"
    assert [L.level for L in rep.levels] == list(range(len(rep.levels)))
    assert all(L.n_nodes >= 1 and L.solve_seconds >= 0 for L in rep.levels)
    # every level covers all elements still being split
    assert rep.levels[0].total_size == m.nelems
    _, rep_r = rsb_partition_mesh(m, 8, tol=1e-3, engine="recursive")
    assert rep_r.levels and rep_r.levels[0].n_nodes == 1


def test_sibling_seeds_differ():
    """Regression: `seed + level` gave every sibling the same start vector."""
    seeds = {_node_seed(0, 3, p_lo) for p_lo in range(8)}
    assert len(seeds) == 8
    assert _node_seed(1, 2, 4) != _node_seed(0, 2, 4)


def test_graph_warm_start_plumbed(pebble):
    """warm_start on the graph path matches the mesh path's behaviour:
    no more restarts than a cold noise start, same balance.  The cold
    reference disables the multilevel warm start (which is itself a warm
    start and would beat the geometric one — see test_multilevel.py)."""
    m, g = pebble
    _, rep_cold = rsb_partition_graph(g, 8, coords=m.coords, tol=1e-3,
                                      warm_start=False, multilevel=False)
    p_warm, rep_warm = rsb_partition_graph(g, 8, coords=m.coords, tol=1e-3,
                                           warm_start=True, multilevel=False)
    assert rep_warm.total_iterations <= rep_cold.total_iterations
    counts = np.bincount(p_warm, minlength=8)
    assert counts.max() - counts.min() <= 1


def test_partition_front_door_engine_flag(box):
    # refine="none" pins the raw driver labels (the ≤1-element invariant is
    # the bisector's; the default repair/refine post stage trades up to
    # balance_tol of it for cut — covered in test_pipeline).
    m, g = box
    pb = partition(m, 4, partitioner="rsb", engine="batched", tol=1e-2,
                   max_restarts=10, refine="none")
    pr = partition(m, 4, partitioner="rsb", engine="recursive", tol=1e-2,
                   max_restarts=10, refine="none")
    for p in (pb, pr):
        counts = np.bincount(p, minlength=4)
        assert counts.max() - counts.min() <= 1
    # default (refined) front door: balance within the post-stage corridor
    pd = partition(m, 4, partitioner="rsb", engine="batched", tol=1e-2,
                   max_restarts=10)
    counts = np.bincount(pd, minlength=4)
    assert counts.max() <= 1.06 * counts.mean()
    with pytest.raises(ValueError):
        rsb_partition_mesh(m, 4, engine="nope")


# ---------------------------------------------------------------------------
# Segmented level reorder: the labels of the per-node reorder it replaced
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shuffled_cube():
    m = box_mesh(16, 16, 16)
    return m.take(np.random.default_rng(3).permutation(m.nelems))


@pytest.mark.parametrize("nparts", [16, 64])
def test_default_labels_match_per_node_reorder(shuffled_cube, nparts,
                                               monkeypatch):
    pipe = make_pipeline("default")
    parts = pipe.run(shuffled_cube, nparts).parts
    nodes = []

    def per_node_reorder(coords, weights, bounds, *, inertial):
        assert not inertial
        nodes.append(len(bounds) - 1)
        return _reference_segments(coords, weights, bounds), 0

    monkeypatch.setattr("repro.core.rsb.rcb_order_segments", per_node_reorder)
    assert np.array_equal(parts, pipe.run(shuffled_cube, nparts).parts)
    assert nodes == [2 ** lv for lv in range(int(np.log2(nparts)))]


@pytest.mark.parametrize("engine", ["batched", "recursive"])
def test_rib_balance_every_level(box, engine):
    m, _ = box
    for nparts in (4, 8, 16):
        parts, _ = rsb_partition_mesh(m, nparts, pre="rib", tol=1e-2,
                                      max_restarts=10, engine=engine)
        assert _ancestor_balance_ok(parts, nparts), (engine, nparts)


def test_rib_engine_cut_parity_pebble(pebble):
    m, g = pebble
    pb, rb = rsb_partition_mesh(m, 8, pre="rib", tol=1e-3, engine="batched")
    pr, _ = rsb_partition_mesh(m, 8, pre="rib", tol=1e-3, engine="recursive")
    cb = partition_metrics(g, pb, 8).edge_cut
    cr = partition_metrics(g, pr, 8).edge_cut
    assert cb <= 1.05 * cr and cr <= 1.05 * cb
    assert rb.pre == "rib"


def _mesh_adjacency(m):
    """Dense dual-graph adjacency of a hex mesh: ω = vertices shared."""
    inc = np.zeros((m.nelems, m.n_vert))
    inc[np.repeat(np.arange(m.nelems), m.vert_gid.shape[1]),
        m.vert_gid.ravel()] = 1.0
    a = inc @ inc.T
    np.fill_diagonal(a, 0.0)
    return a


def _graph_adjacency(g):
    a = np.zeros((g.n, g.n))
    np.add.at(a, (g.rows, g.indices), g.weights)
    return a


def _tree_nodes(raw, nparts):
    """(level, parts, elements) of each node of the bisection tree that
    the raw labels imply, level by level and in part order."""
    out, level, active = [], 0, [(0, nparts, np.arange(raw.size))]
    while active:
        nxt = []
        for lo, hi, idx in active:
            if hi - lo <= 1 or idx.size <= 1:
                continue
            out.append((level, hi - lo, idx))
            mid = lo + (hi - lo) // 2
            left = raw[idx] < mid
            nxt += [(lo, mid, idx[left]), (mid, hi, idx[~left])]
        active, level = nxt, level + 1
    return out


_LAMBDA2_INPUTS = {
    "box666": lambda: box_mesh(6, 6, 6),
    "grid": lambda: grid_graph_2d(24, 16),
    "rmat": _rmat_core,
}


@pytest.mark.parametrize("nparts", [4, 16])
@pytest.mark.parametrize("name", list(_LAMBDA2_INPUTS))
def test_every_tree_node_lambda2_record_matches_dense_eigh(name, nparts):
    """Each node's eigenvalue record against the float64 λ₂ of the
    subgraph the node's elements induce (0 where it is disconnected,
    relative to its mean weighted degree then): 1e-3 at the top node,
    0.07 below it, the limits of the benchmark's check."""
    obj = _LAMBDA2_INPUTS[name]()
    adj = (_mesh_adjacency(obj) if hasattr(obj, "vert_gid")
           else _graph_adjacency(obj))
    ctx = make_pipeline("default").run(obj, nparts)
    nodes = _tree_nodes(np.asarray(ctx.parts_raw), nparts)
    records = ctx.report.records
    assert [(r.level, r.nparts, r.size) for r in records] == \
        [(lv, p, idx.size) for lv, p, idx in nodes]
    errs = []
    for (_, _, idx), r in zip(nodes, records):
        sub = adj[np.ix_(idx, idx)]
        rows, cols = np.nonzero(sub)
        if np.unique(connected_labels(idx.size, rows, cols)).size > 1:
            lam, scale = 0.0, sub.sum() / idx.size
        else:
            lam = np.linalg.eigvalsh(np.diag(sub.sum(1)) - sub)[1]
            scale = lam
        errs.append(abs(r.eigenvalue - lam) / scale)
    assert errs[0] <= 1e-3
    assert max(errs[1:]) <= 0.07
