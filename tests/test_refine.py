"""Post-bisection repair/refinement invariants (the pipeline quality stage).

Property tests (hypothesis) on random connected graphs: the post stage
never increases the edge cut, never leaves the weight-balance corridor,
and leaves a part disconnected only where the repair reports a fragment
it had no room for.  Plus hand-checkable repair semantics (fragment → max shared
weight, ties toward the lighter part) and FM balance-guard cases.
"""

import numpy as np
import pytest

from repro.core import (
    balance_corridor,
    edge_cut,
    partition_metrics,
    refine_boundary,
    repair_components,
    repair_refine,
    run_post_stages,
)
from repro.mesh import build_csr, grid_graph_2d

# Property tests run under hypothesis when the dev dependency is present
# (requirements-dev.txt); otherwise the same invariant checks run over a
# deterministic parameter grid, so the invariants are exercised either way.
try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - container without dev deps
    HAVE_HYPOTHESIS = False

SETTINGS = dict(max_examples=25, deadline=None)

_GRID = [
    (16, 0, 2, 0), (23, 9, 3, 7), (35, 20, 4, 11), (48, 31, 5, 3),
    (64, 45, 2, 19), (80, 60, 3, 23), (57, 12, 4, 29), (72, 50, 5, 31),
]


def _property(func):
    """@given when hypothesis is available, else a fixed parameter grid."""
    if HAVE_HYPOTHESIS:
        return settings(**SETTINGS)(given(
            n=st.integers(16, 80),
            extra=st.integers(0, 60),
            nparts=st.integers(2, 5),
            seed=st.integers(0, 1000),
        )(func))
    return pytest.mark.parametrize("n,extra,nparts,seed", _GRID)(func)


def random_connected_graph(n: int, extra_edges: int, seed: int):
    """Random spanning tree + extra random edges: connected by construction."""
    rng = np.random.default_rng(seed)
    attach = rng.integers(0, np.arange(1, n))  # node i attaches below i
    src = np.arange(1, n, dtype=np.int64)
    dst = attach.astype(np.int64)
    if extra_edges:
        es = rng.integers(0, n, extra_edges)
        ed = rng.integers(0, n, extra_edges)
        src = np.concatenate([src, es])
        dst = np.concatenate([dst, ed])
    w = rng.integers(1, 5, src.size).astype(np.float64)
    return build_csr(src, dst, n, weights=w)


@_property
def test_repair_refine_invariants_random_connected(n, extra, nparts, seed):
    """Cut non-increasing, balance corridor held, and zero disconnected
    parts unless the repair reports fragments left for want of room —
    from arbitrary labels."""
    g = random_connected_graph(n, extra, seed)
    rng = np.random.default_rng(seed + 1)
    parts = rng.integers(0, nparts, n).astype(np.int64)
    # every part nonempty so the label domain is 0..nparts-1 throughout
    parts[rng.choice(n, nparts, replace=False)] = np.arange(nparts)
    w = rng.integers(1, 4, n).astype(np.float64)
    tol = 0.1
    cut0 = edge_cut(g, parts)
    part_w0 = np.bincount(parts, weights=w, minlength=nparts)

    out, stats = repair_refine(g, parts, nparts, weights=w, balance_tol=tol)

    assert stats.cut_after <= cut0 + 1e-9
    assert stats.cut_after == pytest.approx(edge_cut(g, out))
    pm = partition_metrics(g, out, nparts, weights=w)
    assert pm.disconnected_parts == 0 or stats.unrepaired_fragments > 0
    if stats.unrepaired_fragments == 0:
        assert pm.component_count == nparts
    # the balance corridor is [min(floor, initial min), max(cap, initial
    # max)]; no move steps outside it
    part_w = np.bincount(out, weights=w, minlength=nparts)
    cap = max((1 + tol) * part_w0.mean(), part_w0.max())
    floor = min((1 - tol) * part_w0.mean(), part_w0.min())
    assert part_w.max() <= cap + 1e-9
    assert part_w.min() >= floor - 1e-9
    # labels still cover 0..nparts-1
    assert set(np.unique(out)) == set(range(nparts))


@_property
def test_refine_alone_never_worsens(n, extra, nparts, seed):
    g = random_connected_graph(n, extra, seed)
    rng = np.random.default_rng(seed)
    parts = rng.integers(0, nparts, n).astype(np.int64)
    parts[rng.choice(n, nparts, replace=False)] = np.arange(nparts)
    cut0 = edge_cut(g, parts)
    out, stats = refine_boundary(g, parts, nparts)
    assert edge_cut(g, out) <= cut0 + 1e-9
    for s in stats.sweeps:
        assert s.cut_after <= s.cut_before + 1e-9


@_property
def test_kway_stage_invariants_random_connected(n, extra, nparts, seed):
    """The "kway" chain obeys the same contract as the greedy chain: cut
    non-increasing, corridor held, zero disconnected parts unless the
    repair reports fragments left for want of room — from arbitrary
    labels."""
    g = random_connected_graph(n, extra, seed)
    rng = np.random.default_rng(seed + 2)
    parts = rng.integers(0, nparts, n).astype(np.int64)
    parts[rng.choice(n, nparts, replace=False)] = np.arange(nparts)
    w = rng.integers(1, 4, n).astype(np.float64)
    tol = 0.1
    cut0 = edge_cut(g, parts)
    corridor0 = balance_corridor(parts, nparts, w, tol)

    out, stats, _ = run_post_stages(g, parts, nparts, ("repair", "kway"),
                                    weights=w,
                                    post_kw=dict(balance_tol=tol))

    assert stats.cut_after <= cut0 + 1e-9
    assert stats.cut_after == pytest.approx(edge_cut(g, out))
    pm = partition_metrics(g, out, nparts, weights=w)
    assert pm.disconnected_parts == 0 or stats.unrepaired_fragments > 0
    if stats.unrepaired_fragments == 0:
        assert pm.component_count == nparts
    assert stats.corridor == pytest.approx(corridor0)
    part_w = np.bincount(out, weights=w, minlength=nparts)
    assert part_w.max() <= corridor0[1] + 1e-9
    assert part_w.min() >= corridor0[0] - 1e-9
    assert set(np.unique(out)) == set(range(nparts))


def test_second_best_feasible_target_moves():
    """When the best-connected target overflows the cap but a second-best
    part has positive gain and fits, the node must move there (the old
    refiner considered only argmax and skipped the node outright)."""
    # node 0 (p0): conn 5 → p1 (over cap), conn 3 → p2 (fits), internal 1
    g = build_csr(np.array([0, 0, 0, 2, 4]), np.array([1, 2, 4, 3, 5]), 6,
                  weights=np.array([1.0, 5.0, 3.0, 1.0, 1.0]))
    parts = np.array([0, 0, 1, 1, 2, 2], dtype=np.int64)
    w = np.array([1.0, 3.0, 2.0, 2.0, 1.0, 1.0])
    # corridor: cap = max(1.05·8/3, 4) = 4 → p1 (4+1) overflows, p2 (2+1)
    # fits; gains: +4 to p1 (infeasible), +2 to p2 (feasible)
    out, stats = refine_boundary(g, parts, 3, weights=w, balance_tol=0.05)
    assert out[0] == 2
    assert stats.moves_applied == 1
    assert edge_cut(g, out) == 6.0  # 8 − the applied gain of 2


def test_corridor_fixed_across_chained_stages():
    """Every stage in one chain records the corridor computed from the
    chain's INITIAL part weights, and a fragment whose only neighbor part
    sits at the cap stays where it is: the repair never passes the cap."""
    # fragment: node 5 labeled p0 but only adjacent to p1 = {3, 4}, which
    # sits exactly at the cap → repair has no room to move it
    g = build_csr(np.array([0, 5, 3, 6]), np.array([1, 3, 4, 7]), 8)
    parts = np.array([0, 0, 2, 1, 1, 0, 2, 2], dtype=np.int64)
    w = np.array([1.0, 1.0, 1e-4, 1.5, 1.5, 1.0, 1.0, 1.0])
    corridor0 = balance_corridor(parts, 3, w, 0.05)

    out, stats, recs = run_post_stages(g, parts, 3, ("repair", "refine"),
                                       weights=w)

    assert stats.unrepaired_fragments == 1  # no room under the cap
    assert out[5] == 0
    part_w = np.bincount(out, weights=w, minlength=3)
    assert part_w.max() <= corridor0[1] + 1e-9
    corridors = [r.info["corridor"] for r in recs]
    assert corridors[0] == pytest.approx(corridor0)
    # later stages enforce the chain's corridor, not their own
    assert corridors[1] == corridors[0]
    assert stats.corridor == pytest.approx(corridor0)


def test_repair_reassigns_to_max_shared_weight():
    """A fragment goes to the neighbor part sharing the most edge weight."""
    # path 0-1-2-3-4-5; parts: [0,0,1,1,2,2] but node 0 mislabeled as 2:
    # part 2 = {0,4,5} is disconnected (fragment {0}).
    g = build_csr(np.array([0, 1, 2, 3, 4]), np.array([1, 2, 3, 4, 5]), 6,
                  weights=np.array([3.0, 1.0, 1.0, 1.0, 1.0]))
    parts = np.array([2, 0, 1, 1, 2, 2], dtype=np.int64)
    out, stats = repair_components(g, parts, 3)
    assert stats.fragments_repaired == 1
    assert out[0] == 0          # only neighbor part via the weight-3 edge
    assert edge_cut(g, out) < edge_cut(g, parts)
    assert partition_metrics(g, out, 3).disconnected_parts == 0


def test_repair_tie_breaks_to_lighter_part():
    """Equal shared weight → the lighter destination part wins."""
    # Node 0 is a fragment of part 2 (part 2's kept component is the
    # heavier anchor {5, 6}), with one unit edge into part 0 and one into
    # part 1 — an exact tie on shared weight.  Node weights make part 0
    # (10) heavier than part 1 (2), so the tie-break sends 0 to part 1.
    g = build_csr(np.array([0, 0, 5]), np.array([1, 2, 6]), 7)
    parts = np.array([2, 0, 1, 0, 1, 2, 2], dtype=np.int64)
    w = np.array([1.0, 5.0, 1.0, 5.0, 1.0, 1.0, 1.0])
    out, stats = repair_components(g, parts, 3, weights=w)
    assert out[0] == 1
    assert stats.fragments_repaired == 1


def test_refine_respects_balance_cap():
    """FM never moves past the weight corridor even for positive gain."""
    # two triangles joined by a heavy bridge: moving the bridge endpoint
    # would improve the cut but overfill part 1
    src = np.array([0, 1, 2, 3, 4, 5, 2])
    dst = np.array([1, 2, 0, 4, 5, 3, 3])
    w = np.array([1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 10.0])
    g = build_csr(src, dst, 6, weights=w)
    parts = np.array([0, 0, 0, 1, 1, 1], dtype=np.int64)
    out, stats = refine_boundary(g, parts, 2, balance_tol=0.05)
    # cap = 3.15 nodes' weight: any single move to either side violates it
    assert stats.moves_applied == 0
    np.testing.assert_array_equal(out, parts)


def test_refine_never_empties_a_part():
    g = grid_graph_2d(4, 4)
    parts = np.zeros(16, dtype=np.int64)
    parts[5] = 1  # single interior node: every edge is cut, gain positive
    out, _ = refine_boundary(g, parts, 2, balance_tol=10.0)
    assert set(np.unique(out)) == {0, 1}


def test_repair_leaves_global_islands_alone():
    """A fragment with no foreign edges (disconnected input graph) stays."""
    g = build_csr(np.array([0, 2]), np.array([1, 3]), 6)
    # nodes 4, 5 isolated; part 0 = {0,1,4}, part 1 = {2,3,5}
    parts = np.array([0, 0, 1, 1, 0, 1], dtype=np.int64)
    out, stats = repair_components(g, parts, 2)
    np.testing.assert_array_equal(out, parts)
    assert stats.fragments_repaired == 0


def test_sweep_records_track_cut():
    g = grid_graph_2d(12, 12)
    rng = np.random.default_rng(3)
    parts = (np.arange(144) // 72).astype(np.int64)
    flip = rng.choice(144, 20, replace=False)
    parts[flip] = 1 - parts[flip]
    out, stats = refine_boundary(g, parts, 2, sweeps=6)
    assert stats.sweeps, "expected at least one sweep record"
    assert stats.sweeps[0].cut_before == edge_cut(g, parts)
    assert stats.sweeps[-1].cut_after == edge_cut(g, out)
    assert stats.cut_after <= stats.cut_before
