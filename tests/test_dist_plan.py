"""Single-process unit tests for `plan_halo_sharding` invariants.

These run with ONE device (no shard_map): they check the host-side NumPy
planning that the distributed paths (test_distributed.py) build on —
edge coverage, halo = boundary-node count, padding masks, and the
scatter/gather round trip.
"""

import numpy as np
import pytest

from repro.dist.partition_aware import (
    HaloPlan,
    gather_features,
    plan_halo_sharding,
    scatter_features,
)
from repro.mesh.graphs import grid_graph_2d, stencil_graph_3d


@pytest.fixture(scope="module")
def cases():
    rng = np.random.default_rng(0)
    out = []
    g = grid_graph_2d(12, 12)
    out.append((g, rng.integers(0, 4, g.n), 4))          # unbalanced random
    out.append((g, np.arange(g.n) % 6, 6))               # strided
    g3 = stencil_graph_3d(4, 4, 4)
    out.append((g3, rng.integers(0, 5, g3.n), 5))        # 5 parts, 26-stencil
    return out


def test_every_edge_covered_exactly_once(cases):
    for g, parts, nparts in cases:
        plan = plan_halo_sharding(g, parts, nparts)
        # number of real (unmasked) edge slots across shards == directed nnz
        assert int(plan.edge_mask.sum()) == g.nnz
        # and each real slot reproduces a distinct CSR entry: rebuild the
        # dense adjacency from the plan and compare with the oracle
        A = np.zeros((g.n, g.n))
        A[g.rows, g.indices] = g.weights
        B = np.zeros_like(A)
        node_of = np.full((nparts, plan.n_local), -1, np.int64)
        node_of[plan.shard_of, plan.slot_of] = np.arange(g.n)
        # combined index -> global node id, per shard
        exp_node = np.full((nparts, max(plan.halo, 1)), -1, np.int64)
        for s in range(nparts):
            for j in range(plan.halo):
                if plan.export_mask[s, j]:
                    exp_node[s, j] = node_of[s, plan.export_idx[s, j]]
        for s in range(nparts):
            for k in range(plan.max_edges):
                if not plan.edge_mask[s, k]:
                    continue
                dst = node_of[s, plan.edge_dst[s, k]]
                src_c = plan.edge_src[s, k]
                if src_c < plan.n_local:
                    src = node_of[s, src_c]
                else:
                    r, j = divmod(src_c - plan.n_local, plan.halo)
                    src = exp_node[r, j]
                assert src >= 0 and dst >= 0
                assert B[dst, src] == 0, "edge covered twice"
                B[dst, src] = plan.edge_weight[s, k]
        np.testing.assert_allclose(B, A, atol=1e-6)


def test_halo_equals_max_boundary_count(cases):
    for g, parts, nparts in cases:
        plan = plan_halo_sharding(g, parts, nparts)
        cross = parts[g.rows] != parts[g.indices]
        boundary = np.unique(g.indices[cross])            # nodes needed remotely
        per_shard = np.bincount(parts[boundary], minlength=nparts)
        assert plan.halo == int(per_shard.max())
        # per-shard real export rows == that shard's boundary count
        np.testing.assert_array_equal(
            plan.export_mask.sum(1).astype(np.int64), per_shard
        )


def test_padding_rows_fully_masked(cases):
    for g, parts, nparts in cases:
        plan = plan_halo_sharding(g, parts, nparts)
        counts = np.bincount(parts, minlength=nparts)
        np.testing.assert_array_equal(plan.block_sizes, counts)
        # padded node slots receive nothing from scatter
        x = np.ones(g.n)
        blocks = scatter_features(plan, x)
        for s in range(nparts):
            assert blocks[s, : counts[s]].all()
            assert not blocks[s, counts[s]:].any()
        # masked edge/export slots carry zero weight/mask
        assert (plan.edge_weight[plan.edge_mask == 0] == 0).all()
        assert (plan.export_idx[plan.export_mask == 0] == 0).all()


def test_scatter_gather_round_trip(cases):
    rng = np.random.default_rng(3)
    for g, parts, nparts in cases:
        plan = plan_halo_sharding(g, parts, nparts)
        for shape in ((g.n,), (g.n, 7)):
            x = rng.normal(size=shape)
            np.testing.assert_array_equal(
                gather_features(plan, scatter_features(plan, x)), x
            )


def test_collective_words_tracks_cut():
    """Fewer cut edges ⇒ smaller halo ⇒ fewer all_gather words."""
    g = grid_graph_2d(16, 16)
    strips = (np.arange(g.n) // (g.n // 4)).clip(max=3)   # contiguous strips
    scatter = np.arange(g.n) % 4                          # worst case
    p_good = plan_halo_sharding(g, strips, 4)
    p_bad = plan_halo_sharding(g, scatter, 4)
    assert isinstance(p_good, HaloPlan)
    assert p_good.halo < p_bad.halo
    assert (p_good.collective_words_per_feature
            < p_bad.collective_words_per_feature)


def test_pad_to_and_stats():
    """pad_to=8 lane alignment + a JSON-able stats() record."""
    import json

    g = grid_graph_2d(11, 11)                       # odd sizes everywhere
    parts = np.random.default_rng(7).integers(0, 3, g.n)
    plan = plan_halo_sharding(g, parts, 3, pad_to=8)
    assert plan.n_local % 8 == 0
    assert plan.halo % 8 == 0
    assert plan.max_edges % 8 == 0
    # padding stays fully masked and the plan still covers every edge
    assert int(plan.edge_mask.sum()) == g.nnz
    unpadded = plan_halo_sharding(g, parts, 3)
    assert unpadded.halo <= plan.halo < unpadded.halo + 8
    s = plan.stats()
    json.dumps(s)                                   # JSON-able
    assert s["halo"] == plan.halo and 0 < s["edge_fill"] <= 1
    with pytest.raises(ValueError):
        plan_halo_sharding(g, parts, 3, pad_to=0)


def test_plan_validates_inputs():
    g = grid_graph_2d(4, 4)
    with pytest.raises(ValueError):
        plan_halo_sharding(g, np.zeros(5, np.int64), 2)
    with pytest.raises(ValueError):
        plan_halo_sharding(g, np.full(g.n, 3, np.int64), 2)
    plan = plan_halo_sharding(g, np.zeros(g.n, np.int64), 1)
    with pytest.raises(ValueError):
        scatter_features(plan, np.zeros((g.n + 1, 2)))
    with pytest.raises(ValueError):
        gather_features(plan, np.zeros((2, plan.n_local)))
