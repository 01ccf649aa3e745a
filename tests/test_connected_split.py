"""Connected splits in the batched RSB engine (``core/split.py``) and the
repair that holds the balance corridor.

A mesh's spectral halves are connected, so the engine must split it as it
always has: the labels of a small cube are pinned by hash.  A graph whose
subgraph is disconnected is split by components, with eigenvalue 0.  A
graph whose plain split leaves a half in pieces is split into unions of
grown, connected parts.
"""

import hashlib

import numpy as np
import pytest

from repro.configs.parrsb import make_pipeline
from repro.core import (
    balance_corridor,
    partition_metrics,
    repair_components,
    split,
)
from repro.core.fiedler import _pack_layout, ell_layout
from repro.core.rsb import rsb_partition_graph
from repro.mesh import box_mesh, build_csr, grid_graph_2d
from repro.mesh.graphs import connected_labels, rmat_graph, structural_order


@pytest.mark.parametrize("nparts, digest", [
    (4, "8ace14b561e58b63"), (16, "cad8dd5186c90c03")])
def test_cube_labels_are_those_of_the_plain_split(nparts, digest):
    """Every half of the cube's tree is connected, so no component split
    or grown split fires: the labels are those the engine gave before
    either existed."""
    ctx = make_pipeline("default").run(box_mesh(8, 8, 8), nparts)
    parts = np.asarray(ctx.parts, dtype=np.int64)
    assert hashlib.sha256(parts.tobytes()).hexdigest()[:16] == digest
    counters = ctx.trace.total_counters()
    assert counters["comp_split_nodes"] == 0
    assert counters["grown_splits"] == 0


def _two_grids():
    """Two disjoint 12 × 12 lattices, the second's nodes numbered first."""
    g = grid_graph_2d(12, 12)
    src = np.concatenate([g.rows + 144, g.rows])
    dst = np.concatenate([g.indices + 144, g.indices])
    return build_csr(src, dst, 288, symmetrize=False)


def test_a_disconnected_node_reports_lambda2_zero():
    """The root of a two-component graph is not solved whole: its
    eigenvalue is exactly 0, and each component is cut from the other."""
    g = _two_grids()
    parts, rep = rsb_partition_graph(g, 2)
    top = rep.records[0]
    assert top.eigenvalue == 0.0 and top.method == "components"
    assert (parts[:144] == parts[0]).all() and (parts[144:] == parts[144]).all()
    assert parts[0] != parts[144]
    # The halves are connected lattices again: solved as ever below.
    parts4, rep4 = rsb_partition_graph(g, 4)
    assert [r.eigenvalue == 0.0 for r in rep4.records] == [True, False, False]
    assert partition_metrics(g, parts4, 4).disconnected_parts == 0


def test_component_order_is_by_lowest_node_then_value():
    comp = np.array([1, 0, 1, 2, 0, 2, 2])
    values = np.array([0.5, -1.0, -0.5, 3.0, 2.0, 1.0, 2.0])
    # components by lowest node: 1 (node 0), 0 (node 1), 2 (node 3)
    assert split.component_order(comp, values).tolist() == [2, 0, 1, 4, 5, 6, 3]


def test_sign_fixed_makes_the_largest_entry_positive():
    assert split.sign_fixed(np.array([0.1, -0.9, 0.3])).tolist() == [-0.1, 0.9, -0.3]
    assert split.sign_fixed(np.array([0.1, 0.9])).tolist() == [0.1, 0.9]


def test_articulation_points_of_a_masked_subgraph():
    # path 0-1-2-3 with a triangle 3-4-5; node 6 hangs off 4
    g = build_csr(np.array([0, 1, 2, 3, 4, 5, 4]),
                  np.array([1, 2, 3, 4, 5, 3, 6]), 7)
    every = np.ones(7, dtype=bool)
    assert np.flatnonzero(split.articulation(g, every)).tolist() == [1, 2, 3, 4]
    no_six = every.copy()
    no_six[6] = False
    assert np.flatnonzero(split.articulation(g, no_six)).tolist() == [1, 2, 3]


def _hub_and_cliques(k=4, size=6):
    """``k`` cliques of ``size`` nodes, each tied to one hub (node 0) by a
    single edge: every clique hangs off the hub."""
    src, dst = [], []
    for c in range(k):
        base = 1 + c * size
        members = np.arange(base, base + size)
        for i in members:
            for j in members:
                if i < j:
                    src.append(i)
                    dst.append(j)
        src.append(0)
        dst.append(base)
    return build_csr(np.array(src), np.array(dst), 1 + k * size)


@pytest.mark.parametrize("room", [False, True])
def test_repair_never_leaves_the_corridor(room):
    """Cliques tied through one hub, each clique a part (the hub in part
    0), two nodes of clique 1 labelled as clique 2's part: a fragment that
    only part 1 or the hub's part can take.  It moves only where that
    keeps its new part under the cap."""
    g = _hub_and_cliques()
    parts = np.concatenate([[0], np.repeat(np.arange(4), 6)])
    parts[[7, 8]] = 2                    # clique 1's first two nodes
    w = np.ones(g.n)
    # part 1's other four nodes: light (room for the fragment), or heavy
    # enough that part 1 holds the cap and the hub's part is just under it
    w[9:13] = 0.5 if room else 2.0
    corridor = balance_corridor(parts, 4, w, 0.05)
    out, stats = repair_components(g, parts, 4, weights=w, corridor=corridor)
    part_w = np.bincount(out, weights=w, minlength=4)
    floor, cap = corridor
    assert part_w.max() <= cap + 1e-12 and part_w.min() >= floor - 1e-12
    if room:
        assert out[7] == out[8] == 1 and stats.unrepaired_fragments == 0
        assert partition_metrics(g, out, 4, weights=w).disconnected_parts == 0
    else:
        assert out[7] == out[8] == 2 and stats.unrepaired_fragments == 1


def test_the_guard_closer_holds_the_corridor_over_connectivity():
    """The guard's last resort joins a fragment only where a part has room:
    labels inside the corridor whose one fragment has none come back as
    they went in, and the audit afterwards names only the fragment."""
    from repro.guard.policy import GuardReport, check_output, enforce_output

    g = _hub_and_cliques()
    parts = np.concatenate([[0], np.repeat(np.arange(4), 6)])
    parts[[7, 8]] = 2                    # clique 1's first two nodes
    w = np.ones(g.n)
    w[1:7] = 19.5 / 6                    # part 0: the hub and clique 0, 20.5
    w[[7, 8]] = 0.5                      # the fragment, 1
    w[9:13] = 20.5 / 4                   # part 1, 20.5
    w[13:19] = 19.0 / 6                  # part 2, 20 with the fragment
    w[19:25] = 19.0 / 6                  # part 3, 19: mean 20, cap 21
    out = enforce_output(g, parts, 4, weights=w, report=GuardReport())
    np.testing.assert_array_equal(out, parts)
    assert check_output(g, out, 4, weights=w) == [
        "disconnected-parts: 1 fragments"]


def _rmat_core(scale=8, seed=1):
    """The largest component of a small skewed-degree R-MAT graph."""
    g = rmat_graph(1 << scale, 25 << scale, seed=seed)
    lab = connected_labels(g.n, g.rows, g.indices)
    return g.sub(np.flatnonzero(lab == np.bincount(lab).argmax()))


def test_the_structural_order_follows_the_graph_not_its_labels():
    g = _rmat_core()
    perm = np.random.default_rng(0).permutation(g.n)
    a, b = structural_order(g), structural_order(g.sub(perm))
    assert np.array_equal(np.sort(a), np.arange(g.n))
    assert np.array_equal(g.degrees[a], g.degrees[perm[b]])
    # breadth first: every node but the first has a neighbour before it
    pos = np.empty(g.n, dtype=np.int64)
    pos[a] = np.arange(g.n)
    earlier = np.zeros(g.n, dtype=bool)
    np.logical_or.at(earlier, g.rows, pos[g.indices] < pos[g.rows])
    assert earlier.sum() == g.n - 1 and not earlier[a[0]]


def _star_with_tail(leaves=120, tail=200):
    """A hub with ``leaves`` leaves and a path of ``tail`` nodes hung off
    it: its packed ELL spills the hub into overflow rows (R > 0)."""
    path = np.arange(leaves + 1, leaves + tail + 1)
    src = np.concatenate([np.zeros(leaves + 1, np.int64), path[:-1]])
    dst = np.concatenate([np.arange(1, leaves + 1), path])
    return build_csr(src, dst, leaves + tail + 1)


def _edge_weighted_grid(nx=24, ny=16):
    """A lattice with seeded integer edge weights 1–4."""
    g = grid_graph_2d(nx, ny)
    up = g.rows < g.indices
    w = np.random.default_rng(7).integers(1, 5, int(up.sum())).astype(float)
    return build_csr(g.rows[up], g.indices[up], g.n, weights=w)


_ORDER_INPUTS = {
    "rmat": _rmat_core,
    "grid": lambda: grid_graph_2d(24, 16),
    "star-tail": _star_with_tail,
    "grid-edge-weighted": _edge_weighted_grid,
}


def test_the_star_with_a_tail_spills_into_overflow_rows():
    g = _star_with_tail()
    offs, N, *_ = _pack_layout([g.n])
    W, R = ell_layout(g.degrees, N)
    assert R > 0 and W < g.degrees.max()


# A grid with node weights is left out: the structural order keys on
# degrees and edge weights only, so its solves and cut follow the node
# order (a known defect, named in ROADMAP.md).
@pytest.mark.parametrize("name, nparts, seed", [
    pytest.param(name, nparts, seed,
                 id=str(seed) if (name, nparts) == ("rmat", 8)
                 else f"{name}-{nparts}-{seed}")
    for name in _ORDER_INPUTS for nparts in (4, 8, 16) for seed in (1, 2)])
def test_a_graph_without_coordinates_is_solved_alike_in_any_order(
        name, nparts, seed):
    """The same solves, restart for restart, and the same cut, whatever
    order the nodes come in."""
    g = _ORDER_INPUTS[name]()
    perm = np.random.default_rng(seed).permutation(g.n)
    p1, r1 = rsb_partition_graph(g, nparts)
    p2, r2 = rsb_partition_graph(g.sub(perm), nparts)
    assert [(r.size, r.iterations) for r in r1.records] == \
        [(r.size, r.iterations) for r in r2.records]
    back = np.empty_like(p2)
    back[perm] = p2
    assert partition_metrics(g, p1, nparts).edge_cut == \
        partition_metrics(g, back, nparts).edge_cut

