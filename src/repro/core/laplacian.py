"""Assembled Laplacian operators (ELL / CSR) + dense oracle.

The finest level of the paper's multigrid uses the gather-scatter Laplacian
(`repro.core.gather_scatter`); coarser levels and generic-graph inputs use an
assembled form (paper §7: "we generate L₀, L₁, L₂, … as CSR matrices").  On
TPU we store the padded **ELL** layout — static shape, row-contiguous,
VMEM-tileable — and the matvec is the Pallas `ell_spmv` kernel with a pure
jnp fallback.  Both layouts are kernel-backed: 2-D (n, w) operators use the
flat kernel, 3-D (B, n, w) leading-batch-dim operators (the level-synchronous
engine's and the batched AMG's layout) use the batched grid variant.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.mesh.graphs import Graph, csr_to_ell


@dataclasses.dataclass(frozen=True)
class EllLaplacian:
    """L x = deg ⊙ x − A x with A in padded ELL form.

    cols/vals: (n, width) — or (B, n, width) for a **batched** operator
    applying B independent Laplacians to (B, n) vectors in one shot (the
    level-synchronous RSB engine's layout).  Padding entries have val 0
    (col = row id).

    A 2-D operator may split its hub rows (the packed layout's
    `repro.core.fiedler.ell_layout`): cols/vals are then the **head**,
    each row's first `width` entries in CSR order, and the **overflow**
    rows over_cols/over_vals (R, width) hold the rest of every longer row,
    `width` entries per overflow row in CSR order, each summed into row
    over_owner[r] (R,), ascending.  A hub's last overflow row pads with
    col = owner, val 0; padding overflow rows have owner = col = n − 1 and
    val 0, so they add 0.  With no overflow (R = 0) the three fields are
    None: the operator, and every program traced over it, is the plain
    layout's.  `fill_ell_block` writes these invariants.

    Registered as a pytree (cols/vals/diag and the overflow fields are
    leaves; n/use_kernel are static) so a batched solve can take the
    operator as a *traced* jit argument: one compiled trace serves every
    operator of the same shape bucket instead of one trace per instance.
    """

    cols: jax.Array    # (..., n, width) int32
    vals: jax.Array    # (..., n, width) float32 — adjacency weights
    diag: jax.Array    # (..., n) float32 — Σ_j ω_ij (true Laplacian diagonal)
    n: int
    use_kernel: bool = False
    over_cols: jax.Array | None = None   # (R, width) int32
    over_vals: jax.Array | None = None   # (R, width) float32
    over_owner: jax.Array | None = None  # (R,) int32, ascending

    def __hash__(self):
        return id(self)

    def adj_apply(self, x: jax.Array) -> jax.Array:
        if self.cols.ndim == 3:
            if self.use_kernel:
                from repro.kernels.ell_spmv import ops as _ops

                return _ops.ell_spmv_batched(self.cols, self.vals, x)
            B = self.cols.shape[0]
            taken = jnp.take_along_axis(
                x, self.cols.reshape(B, -1), axis=-1
            ).reshape(self.cols.shape)
            return (self.vals * taken).sum(-1)
        if self.use_kernel:
            from repro.kernels.ell_spmv import ops as _ops

            y = _ops.ell_spmv(self.cols, self.vals, x)
        else:
            y = (self.vals * jnp.take(x, self.cols, axis=-1)).sum(-1)
        if self.over_owner is None:
            return y
        spill = (self.over_vals * jnp.take(x, self.over_cols, axis=-1)).sum(-1)
        return y.at[..., self.over_owner].add(spill, indices_are_sorted=True)

    def apply(self, x: jax.Array) -> jax.Array:
        return self.diag * x - self.adj_apply(x)

    def __call__(self, x: jax.Array) -> jax.Array:
        return self.apply(x)


jax.tree_util.register_dataclass(
    EllLaplacian,
    data_fields=("cols", "vals", "diag", "over_cols", "over_vals",
                 "over_owner"),
    meta_fields=("n", "use_kernel"),
)


def spill_rows(degrees: np.ndarray, width: int) -> np.ndarray:
    """Overflow rows each node needs past an ELL head of `width` slots:
    ⌈deg / width⌉ − 1, and 0 for a node of degree 0."""
    return np.maximum(np.asarray(degrees, np.int64) - 1, 0) // width


def fill_ell_block(graph: Graph, C: np.ndarray, V: np.ndarray, D: np.ndarray,
                   col_offset: int = 0, over=None, at: int = 0) -> int:
    """Fill one graph's rows of a padded ELL block (C/V/D are views of the
    target rows; rows past graph.n keep self-columns and zero vals/diag,
    so L acts as 0 on them).  The single home of the padding invariants —
    the padded, batched, and packed builders all delegate here.

    Row i's first width = C.shape[1] entries, in CSR order, fill its head
    row (the rest of the row: col = i + col_offset, val 0).  The entries
    past them spill `width` at a time into the overflow rows `over` =
    (cols, vals, owner), from row `at` on, owner i + col_offset (the
    `EllLaplacian` layout); returns the next free overflow row.  Overflow
    rows past the last one filled keep what the caller put there."""
    width = C.shape[1]
    extra = spill_rows(graph.degrees, width)
    n_over = int(extra.sum())
    if n_over and (over is None or at + n_over > over[2].shape[0]):
        raise ValueError("ELL layout below the row degrees")
    own = np.arange(graph.n, dtype=np.int64) + col_offset
    rows = graph.rows
    pos = np.arange(graph.nnz, dtype=np.int64) - graph.indptr[rows]
    head = pos < width
    C[:graph.n] = own[:, None]
    V[:graph.n] = 0.0
    C[rows[head], pos[head]] = graph.indices[head] + col_offset
    V[rows[head], pos[head]] = graph.weights[head]
    np.add.at(D[:graph.n], rows, graph.weights)
    if n_over:
        OC, OV, OO = over
        OO[at:at + n_over] = np.repeat(own, extra)
        OC[at:at + n_over] = OO[at:at + n_over, None]
        OV[at:at + n_over] = 0.0
        tail = ~head
        first = at + np.cumsum(extra) - extra   # each node's first row
        r = first[rows[tail]] + pos[tail] // width - 1
        OC[r, pos[tail] % width] = graph.indices[tail] + col_offset
        OV[r, pos[tail] % width] = graph.weights[tail]
    return at + n_over


def ell_laplacian_batched(
    graphs: list, n_pad: int, width_pad: int, b_pad: int,
    *, use_kernel: bool = False,
) -> EllLaplacian:
    """Stack B assembled Laplacians into one (b_pad, n_pad, width_pad) ELL
    operator.  Rows past each graph's n — and whole batch-padding rows —
    have zero vals and zero diag, so L acts as 0 on them."""
    C = np.tile(
        np.arange(n_pad, dtype=np.int64)[None, :, None], (b_pad, 1, width_pad)
    )
    V = np.zeros((b_pad, n_pad, width_pad), dtype=np.float64)
    D = np.zeros((b_pad, n_pad), dtype=np.float64)
    for b, g in enumerate(graphs):
        fill_ell_block(g, C[b], V[b], D[b])
    return EllLaplacian(
        cols=jnp.asarray(C.astype(np.int32)),
        vals=jnp.asarray(V.astype(np.float32)),
        diag=jnp.asarray(D.astype(np.float32)),
        n=n_pad,
        use_kernel=use_kernel,
    )


def ell_laplacian(graph: Graph, *, use_kernel: bool = False) -> EllLaplacian:
    cols, vals = csr_to_ell(graph)
    deg = np.zeros(graph.n, dtype=np.float64)
    np.add.at(deg, graph.rows, graph.weights)
    return EllLaplacian(
        cols=jnp.asarray(cols.astype(np.int32)),
        vals=jnp.asarray(vals.astype(np.float32)),
        diag=jnp.asarray(deg.astype(np.float32)),
        n=graph.n,
        use_kernel=use_kernel,
    )


def dense_laplacian_np(graph: Graph) -> np.ndarray:
    """Dense float64 Laplacian — the test oracle."""
    A = np.zeros((graph.n, graph.n), dtype=np.float64)
    A[graph.rows, graph.indices] = graph.weights
    return np.diag(A.sum(1)) - A


def fiedler_oracle_np(graph: Graph) -> tuple[float, np.ndarray]:
    """(λ₂, y₂) by dense eigendecomposition — ground truth for small graphs."""
    L = dense_laplacian_np(graph)
    w, v = np.linalg.eigh(L)
    return float(w[1]), v[:, 1]
