"""Pallas kernels vs pure-jnp oracles: shape × dtype sweeps (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ell_spmv.ops import ell_spmv, ell_spmv_batched, lap_apply
from repro.kernels.ell_spmv.ref import ell_spmv_batched_ref, ell_spmv_ref, lap_apply_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(
        atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("n,w", [(128, 4), (256, 27), (1000, 8), (4096, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_spmv_sweep(n, w, dtype):
    cols = jnp.asarray(RNG.integers(0, n, (n, w)), jnp.int32)
    vals = jnp.asarray(RNG.normal(size=(n, w)), dtype)
    x = jnp.asarray(RNG.normal(size=(n,)), dtype)
    out = ell_spmv(cols, vals, x, prefer="pallas")
    ref = ell_spmv_ref(cols.T, vals.T, x)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_lap_apply_kernel_matches_ref():
    n, w = 512, 6
    cols = jnp.asarray(RNG.integers(0, n, (n, w)), jnp.int32)
    vals = jnp.asarray(np.abs(RNG.normal(size=(n, w))), jnp.float32)
    diag = jnp.asarray(np.asarray(vals).sum(1))
    x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    out = lap_apply(cols, vals, diag, x, prefer="pallas")
    ref = lap_apply_ref(cols.T, vals.T, diag, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,n,w", [(2, 256, 8), (3, 1000, 5), (4, 128, 27),
                                   (1, 512, 6)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ell_spmv_batched_sweep(B, n, w, dtype):
    cols = jnp.asarray(RNG.integers(0, n, (B, n, w)), jnp.int32)
    vals = jnp.asarray(RNG.normal(size=(B, n, w)), dtype)
    x = jnp.asarray(RNG.normal(size=(B, n)), dtype)
    out = ell_spmv_batched(cols, vals, x, prefer="pallas")
    ref = ell_spmv_batched_ref(cols.swapaxes(-1, -2), vals.swapaxes(-1, -2), x)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_batched_laplacian_kernel_matches_fallback():
    """Regression for the silent `use_kernel=True` no-op on batched
    (ndim==3) EllLaplacian operators: the kernel and pure-jnp paths must
    agree on real padded engine operators."""
    import dataclasses

    from repro.core.laplacian import ell_laplacian_batched
    from repro.mesh import grid_graph_2d

    graphs = [grid_graph_2d(16, 16), grid_graph_2d(10, 20)]
    op = ell_laplacian_batched(graphs, 256, 8, 2)
    opk = dataclasses.replace(op, use_kernel=True)
    x = jnp.asarray(RNG.normal(size=(2, 256)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(op.apply(x)), np.asarray(opk.apply(x)), atol=2e-5
    )


def test_batched_inverse_kernel_path_matches_oracle():
    """use_kernel=True on the batched inverse path (3-D operators through
    the batched Pallas grid) reaches the same Fiedler eigenvalue."""
    from repro.core import fiedler_from_graph_batched, fiedler_oracle_np
    from repro.mesh import grid_graph_2d

    g = grid_graph_2d(18, 24)
    lam, _ = fiedler_oracle_np(g)
    res = fiedler_from_graph_batched([g], method="inverse", tol=1e-4,
                                     use_kernel=True)[0]
    assert res.eigenvalue == pytest.approx(lam, rel=2e-2, abs=1e-4)


def test_ell_kernel_used_by_fiedler():
    """use_kernel=True path of the ELL Laplacian reaches the same Fiedler
    eigenvalue as the jnp path."""
    from repro.core import fiedler_from_graph, fiedler_oracle_np
    from repro.mesh import grid_graph_2d

    g = grid_graph_2d(18, 12)
    lam, _ = fiedler_oracle_np(g)
    res = fiedler_from_graph(g, method="lanczos", tol=1e-4, use_kernel=True)
    assert res.eigenvalue == pytest.approx(lam, rel=2e-2, abs=1e-4)


# ---------------------------------------------------------------------------
# segment_sum: the (boundary × nparts) connection table
# ---------------------------------------------------------------------------

def _conn_numpy(labels, cols, wts, nparts):
    """Independent numpy oracle (np.add.at scatter)."""
    out = np.zeros((cols.shape[0], nparts), np.float32)
    ri, ki = np.nonzero(np.ones_like(np.asarray(wts), bool))
    np.add.at(out, (ri, np.asarray(labels)[np.asarray(cols)[ri, ki]]),
              np.asarray(wts)[ri, ki])
    return out


@pytest.mark.parametrize("B,w,m,nparts", [
    (37, 5, 120, 13),     # odd everything
    (8, 1, 9, 1),         # single part, single slot
    (256, 27, 300, 64),   # block-aligned
    (130, 3, 200, 129),   # nparts just past one lane tile
    (5, 4, 16, 2),        # tiny
])
def test_segment_sum_parity(B, w, m, nparts):
    from repro.kernels.segment_sum.ops import connection_table

    labels = jnp.asarray(RNG.integers(0, nparts, m), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, m, (B, w)), jnp.int32)
    wts = jnp.asarray(RNG.integers(1, 5, (B, w)), jnp.float32)
    oracle = _conn_numpy(labels, cols, wts, nparts)
    for prefer in ("pallas", "ref", "auto"):
        out = connection_table(labels, cols, wts, nparts, prefer=prefer)
        np.testing.assert_array_equal(np.asarray(out), oracle), prefer


def test_segment_sum_empty_boundary():
    from repro.kernels.segment_sum.ops import connection_table

    labels = jnp.zeros((7,), jnp.int32)
    out = connection_table(labels, jnp.zeros((0, 4), jnp.int32),
                           jnp.zeros((0, 4), jnp.float32), 7)
    assert out.shape == (0, 7)


def test_segment_sum_padding_is_inert():
    """Weight-0 padding entries contribute nothing regardless of col."""
    from repro.kernels.segment_sum.ops import connection_table

    labels = jnp.asarray([0, 1, 2, 1], jnp.int32)
    cols = jnp.asarray([[1, 3, 0], [2, 0, 0]], jnp.int32)
    wts = jnp.asarray([[2.0, 5.0, 0.0], [3.0, 0.0, 0.0]], jnp.float32)
    for prefer in ("pallas", "ref"):
        out = np.asarray(connection_table(labels, cols, wts, 3,
                                          prefer=prefer))
        np.testing.assert_array_equal(out, [[0.0, 7.0, 0.0],
                                            [0.0, 0.0, 3.0]])


@pytest.mark.parametrize("G,B,w,m,nparts", [(3, 40, 6, 90, 9),
                                            (1, 64, 2, 30, 4),
                                            (5, 17, 3, 50, 33)])
def test_segment_sum_batched_parity(G, B, w, m, nparts):
    """Batched launch ≡ per-problem single launches ≡ numpy oracle."""
    from repro.kernels.segment_sum.ops import (connection_table,
                                               connection_table_batched)

    labels = jnp.asarray(RNG.integers(0, nparts, (G, m)), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, m, (G, B, w)), jnp.int32)
    wts = jnp.asarray(RNG.integers(1, 5, (G, B, w)), jnp.float32)
    for prefer in ("pallas", "ref"):
        out = np.asarray(connection_table_batched(labels, cols, wts, nparts,
                                                  prefer=prefer))
        for g in range(G):
            single = connection_table(labels[g], cols[g], wts[g], nparts,
                                      prefer=prefer)
            np.testing.assert_array_equal(out[g], np.asarray(single))
            np.testing.assert_array_equal(
                out[g], _conn_numpy(labels[g], cols[g], wts[g], nparts))
