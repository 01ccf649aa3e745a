"""Hub-split packed ELL: a head of about the mean degree plus overflow rows
for the hubs (`fiedler.ell_layout`, `laplacian.fill_ell_block`), against the
dense Laplacian, the plain layout it must leave alone on a mesh, and the
run-wide pin of the RSB engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fiedler, lanczos, rsb
from repro.core.fiedler import (
    _DENSE_CUTOFF,
    _pack_layout,
    _packed_ell_laplacian,
    ell_layout,
    next_pow2,
)
from repro.core.laplacian import EllLaplacian, dense_laplacian_np, spill_rows
from repro.mesh import box_mesh, dual_graph
from repro.mesh.graphs import build_csr, connected_labels, csr_to_ell, rmat_graph


def _largest_component(g):
    lab = connected_labels(g.n, g.rows, g.indices)
    return g.sub(np.flatnonzero(lab == np.argmax(np.bincount(lab))))


def _rmat(n, seed):
    """R-MAT at the ogbn-products edge factor, largest component."""
    return _largest_component(rmat_graph(n, int(round(25.26 * n)), seed=seed))


def _star(leaves):
    return build_csr(np.zeros(leaves), np.arange(1, leaves + 1), leaves + 1)


def _plain_packed_ell(graphs, offs, N, width):
    """The packed builder with every row `width` slots wide and no
    overflow: the layout every ELL had before hub rows could spill."""
    C = np.tile(np.arange(N, dtype=np.int64)[:, None], (1, width))
    V = np.zeros((N, width))
    D = np.zeros(N)
    for b, g in enumerate(graphs):
        o = int(offs[b])
        cols, vals = csr_to_ell(g)
        C[o:o + g.n, :cols.shape[1]] = cols + o
        V[o:o + g.n, :cols.shape[1]] = vals
        np.add.at(D[o:o + g.n], g.rows, g.weights)
    return EllLaplacian(cols=jnp.asarray(C.astype(np.int32)),
                        vals=jnp.asarray(V.astype(np.float32)),
                        diag=jnp.asarray(D.astype(np.float32)), n=N)


def _packed(graphs):
    offs, N, *_ = _pack_layout([g.n for g in graphs])
    W, R = ell_layout(np.concatenate([g.degrees for g in graphs]), N)
    return offs, N, W, R


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("case", ["star", "rmat", "two_packed"])
def test_split_apply_matches_the_dense_laplacian(case, use_kernel):
    graphs = {"star": [_star(40)], "rmat": [_rmat(512, 4)],
              "two_packed": [_star(40), _rmat(256, 3)]}[case]
    offs, N, W, R = _packed(graphs)
    assert R > 0 and W < max(g.degrees.max() for g in graphs)
    op = _packed_ell_laplacian(graphs, offs, N, W, R)
    if use_kernel:
        op = dataclasses.replace(op, use_kernel=True)
    assert op.cols.shape == (N, W) and op.over_cols.shape == (R, W)
    owner = np.asarray(op.over_owner)
    assert (np.diff(owner) >= 0).all()
    x = np.random.default_rng(0).standard_normal(N).astype(np.float32)
    want = np.zeros(N)
    for b, g in enumerate(graphs):
        o = int(offs[b])
        want[o:o + g.n] = dense_laplacian_np(g) @ x[o:o + g.n]
    got = np.asarray(jax.jit(lambda op, v: op.apply(v))(op, jnp.asarray(x)))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=1e-5 * scale, rtol=0)


def test_a_box_mesh_keeps_the_plain_layout_and_its_program():
    """The cube's dual graph: mean degree 19.8 and largest 26, so the head
    is the plain width 32 and no row spills."""
    g = dual_graph(box_mesh(8, 8, 8))
    offs, N, W, R = _packed([g])
    assert (N, W, R) == (512, 32, 0)
    op = _packed_ell_laplacian([g], offs, N, W, R)
    plain = _plain_packed_ell([g], offs, N, 32)
    assert op.over_cols is None and op.over_vals is None
    assert op.over_owner is None
    for name in ("cols", "vals", "diag"):
        a, b = np.asarray(getattr(op, name)), np.asarray(getattr(plain, name))
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    x = jnp.ones(N, jnp.float32)

    def program(o):
        return str(jax.make_jaxpr(lambda o, v: o.apply(v))(o, x))

    assert program(op) == program(plain)


def test_a_box_mesh_partition_matches_the_plain_builder(monkeypatch):
    from repro.configs.parrsb import make_pipeline

    mesh = box_mesh(8, 8, 8)
    labels = make_pipeline("default").run(mesh, 8).parts

    def plain(graphs, offs, N, width, rows=0):
        assert rows == 0
        return _plain_packed_ell(graphs, offs, N, width)

    monkeypatch.setattr(fiedler, "_packed_ell_laplacian", plain)
    assert np.array_equal(make_pipeline("default").run(mesh, 8).parts, labels)


def test_the_chooser_on_an_rmat_graph_and_its_edges():
    """R-MAT over 2,048 vertices at edge factor 25.26, seed 1, largest
    component: 1,822 nodes, 65,144 stored entries, mean degree 35.75 and
    largest 941.  Head next_pow2(36) = 64 against the plain 1,024; 501
    overflow rows, padded to 512; (2,048 + 512) · 64 = 163,840 slots
    against 2,097,152 plain, under half, so the split is taken."""
    g = _rmat(2048, 1)
    d = g.degrees
    assert (g.n, g.nnz, int(d.max())) == (1822, 65144, 941)
    assert int(np.ceil(d.mean())) == 36
    assert int(spill_rows(d, 64).sum()) == 501
    assert ell_layout(d, 2048) == (64, 512)
    # no node is wider than its head: the plain layout, whatever N is
    assert ell_layout(np.full(100, 7), 128) == (8, 0)
    assert ell_layout(np.zeros(5, np.int64), 8) == (2, 0)
    # a star: head 2, 19 overflow rows padded to 32, 192 slots of 4,096
    assert ell_layout(np.array([40] + [1] * 40), 64) == (2, 32)
    # one row past a head of 32: (64 + 1) · 32 slots is over half of
    # 64 · 64, so the plain layout stays
    assert ell_layout(np.array([40] + [30] * 40), 64) == (64, 0)


def test_the_root_pin_holds_every_level_in_one_program(monkeypatch):
    """Default pipeline on an R-MAT graph with hubs (472 nodes, largest
    degree 314): every packed level is built with the root's (W, R),
    whose overflow rows hold the level's own spill, and
    `_packed_restart` compiles once."""
    from repro.configs.parrsb import make_pipeline

    g = _rmat(512, 4)
    pin = ell_layout(g.degrees, next_pow2(g.n))
    assert pin[1] > 0
    calls, shapes = [], set()
    real_batched = rsb.fiedler_from_graph_batched
    real_build = fiedler._packed_ell_laplacian

    def batched(problems, **kw):
        calls.append(([p for p in problems if p.n > _DENSE_CUTOFF],
                      kw["width_pad"], kw["overflow_rows"]))
        return real_batched(problems, **kw)

    def build(graphs, offs, N, width, rows=0):
        op = real_build(graphs, offs, N, width, rows)
        shapes.add(tuple(a.shape for a in jax.tree.leaves(op)))
        return op

    monkeypatch.setattr(rsb, "fiedler_from_graph_batched", batched)
    monkeypatch.setattr(fiedler, "_packed_ell_laplacian", build)
    lanczos._packed_restart.clear_cache()
    make_pipeline("default").run(g, 8)
    solved = [c for c in calls if c[0]]
    assert len(solved) >= 2
    for problems, W, R in solved:
        assert (W, R) == pin
        assert spill_rows(np.concatenate([p.degrees for p in problems]),
                          W).sum() <= R
    assert len(shapes) == 1
    assert lanczos._packed_restart._cache_size() == 1
