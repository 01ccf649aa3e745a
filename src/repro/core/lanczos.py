"""Lanczos with restarts for the Fiedler pair (paper §6).

A fixed-width Lanczos window (full reorthogonalization — necessary in fp32)
runs as one jitted `lax.scan`; the small tridiagonal Ritz problem is solved
with `jnp.linalg.eigh`; the smallest Ritz vector restarts the window.  The
constant vector is deflated explicitly at every step (paper Eq. 4.11).

Residual estimate: the classic `|β_m · s_m|` bound (last component of the
Ritz eigenvector scaled by the final off-diagonal), refined with one true
matvec at restart boundaries.

**Batched variant** (`lanczos_fiedler_batched`): runs B independent Fiedler
solves — all bisections of one RSB tree level — through a single jitted
restart step.  The subproblems are **packed** into one flat (N,) vector
(each problem owns a contiguous, zero-padded block; `seg[j]` names slot
j's problem) and every per-problem reduction (α, β, reorthogonalization
dots, constant deflation, Ritz-vector norms) becomes a one-hot
segment matmul, while the small tridiagonal Ritz problems are solved with
one vmapped `eigh` over the segment axis.  The operator is a block-diagonal
*pytree* (`EllLaplacian`/`GSLaplacian` over the packed slots) passed as a
traced argument, so the compiled trace is keyed only by
(N, n_seg, window) and the operator's shapes — for an ELL operator its
head width W and overflow rows R (`fiedler.ell_layout`), which the RSB
engine pins per run at the root: because a tree level's subproblems
partition the root set, every level of a run — and every run on the same
mesh — reuses ONE trace, with no padded-lane compute.  Convergence is
tracked per subproblem on the host; a converged problem's Ritz output is
frozen while the remaining segments keep iterating.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.flexcg import _project_out_ones


@dataclasses.dataclass
class LanczosInfo:
    restarts: int
    eigenvalue: float
    residual: float
    converged: bool
    breakdown: bool = False  # non-finite Ritz pair: (θ, res) are unusable


@dataclasses.dataclass
class BatchedLanczosInfo:
    """Per-subproblem convergence bookkeeping for a batched solve."""

    restarts: np.ndarray     # (B,) restart count at convergence (or the cap)
    eigenvalue: np.ndarray   # (B,)
    residual: np.ndarray     # (B,)
    converged: np.ndarray    # (B,) bool
    breakdown: np.ndarray | None = None  # (B,) bool: frozen on a stale pair


def _window_body(op, q0, mask, m):
    """One restart window: returns (Q (m,n), alpha (m,), beta (m,)).

    beta[j] is the subdiagonal linking step j to j+1 (beta[m-1] is the
    residual coupling used in the Ritz residual bound).
    """
    n = q0.shape[0]

    def step(carry, j):
        Q, q, q_prev, beta_prev = carry
        w = op(q) - beta_prev * q_prev
        alpha = jnp.sum(w * q)
        w = w - alpha * q
        # Full reorthogonalization against the window + constants (twice is
        # enough — Parlett): rows ≥ j of Q are zero so the mask is implicit.
        for _ in range(2):
            w = w - Q.T @ (Q @ w)
            w = _project_out_ones(w, mask)
        beta = jnp.linalg.norm(w)
        q_next = jnp.where(beta > 1e-12, w / jnp.maximum(beta, 1e-30), 0.0)
        Q = Q.at[j].set(q)
        return (Q, q_next, q, beta), (alpha, beta)

    Q0 = jnp.zeros((m, n), q0.dtype)
    (Q, _, _, _), (alpha, beta) = jax.lax.scan(
        step, (Q0, q0, jnp.zeros_like(q0), jnp.asarray(0.0, q0.dtype)),
        jnp.arange(m),
    )
    return Q, alpha, beta


# Two jit forms of the window.  Operator dataclasses (EllLaplacian /
# GSLaplacian — registered pytrees) go in as TRACED arguments: one compiled
# trace serves every operator of the same shape, so the recursive engine no
# longer retraces per tree node.  Plain callables (e.g. the deflated
# closure in `fiedler_pair_from_graph`) fall back to the static form, one
# trace per callable identity.
_lanczos_window_pytree = partial(jax.jit, static_argnames=("m",))(_window_body)
_lanczos_window = partial(jax.jit, static_argnums=(0, 3))(_window_body)


@jax.jit
def _apply_pytree_op(op, x):
    """Module-level jitted matvec for pytree operators (shared cache)."""
    return op(x)


def _run_window(op, q, mask, m):
    if dataclasses.is_dataclass(op):
        return _lanczos_window_pytree(op, q, mask, m=m)
    return _lanczos_window(op, q, mask, m)


def _tridiag_eigh(alpha: jax.Array, beta: jax.Array):
    m = alpha.shape[0]
    T = jnp.diag(alpha) + jnp.diag(beta[:-1], 1) + jnp.diag(beta[:-1], -1)
    return jnp.linalg.eigh(T)


def lanczos_fiedler(
    op: Callable[[jax.Array], jax.Array],
    n: int,
    *,
    mask: jax.Array | None = None,
    key: jax.Array | None = None,
    b0: jax.Array | None = None,
    window: int = 30,
    max_restarts: int = 50,
    tol: float = 1e-3,
) -> tuple[jax.Array, LanczosInfo]:
    """Return (y₂ approximation, info)."""
    mask = jnp.ones((n,), jnp.float32) if mask is None else mask.astype(jnp.float32)
    if b0 is None:
        key = jax.random.PRNGKey(0) if key is None else key
        q = jax.random.normal(key, (n,), jnp.float32)
    else:
        q = b0.astype(jnp.float32)
    q = _project_out_ones(q, mask)
    q = q / jnp.maximum(jnp.linalg.norm(q), 1e-30)

    if dataclasses.is_dataclass(op):
        opj = partial(_apply_pytree_op, op)
    else:
        opj = jax.jit(op)
    theta = jnp.asarray(0.0)
    res = jnp.asarray(jnp.inf)
    y = q
    converged = False
    r = 0
    for r in range(1, max_restarts + 1):
        Q, alpha, beta = _run_window(op, q, mask, window)
        evals, evecs = _tridiag_eigh(alpha, beta)
        s = evecs[:, 0]
        theta = evals[0]
        y = Q.T @ s
        ynorm = jnp.maximum(jnp.linalg.norm(y), 1e-30)
        y = y / ynorm
        # Cheap bound, then the true residual (one matvec).
        Ly = opj(y)
        res = jnp.linalg.norm(Ly - theta * y)
        if float(res) <= tol * max(float(theta), 1e-12):
            converged = True
            break
        q = _project_out_ones(y, mask)
        q = q / jnp.maximum(jnp.linalg.norm(q), 1e-30)

    info = LanczosInfo(
        restarts=r,
        eigenvalue=float(theta),
        residual=float(res),
        converged=converged,
        breakdown=not (np.isfinite(float(theta))
                       and np.isfinite(float(res))),
    )
    return y, info


# ---------------------------------------------------------------------------
# Batched (level-synchronous, packed) Lanczos
# ---------------------------------------------------------------------------

def _seg_onehot(seg: jax.Array, n_seg: int, dtype) -> jax.Array:
    """(n_seg, N) one-hot segment matrix: per-problem reductions as matmuls
    (dense GEMMs beat scatter-adds on every backend for these sizes)."""
    return (seg[None, :] == jnp.arange(n_seg, dtype=seg.dtype)[:, None]).astype(dtype)


def _project_out_ones_seg(x, mask, seg, S):
    """Per-problem constant deflation: x ← (x − mean_mask,p(x)) · mask."""
    s = S @ (x * mask)
    c = jnp.maximum(S @ mask, 1.0)
    return (x - (s / c)[seg]) * mask


@partial(jax.jit, static_argnames=("n_seg", "window"))
def _packed_restart(op, q, mask, seg, n_seg, window):
    """One jitted restart over all packed subproblems.

    `op` is a block-diagonal pytree operator over the packed (N,) slots,
    passed as a *traced* argument — the compile cache is keyed by
    (N, n_seg, window) and the operator's shapes (an ELL's (W, R)), not
    by operator instance, so one trace serves every level of a run (and
    every run sharing the shape).  Empty segments
    (padding) produce θ = 0, res = 0 and read as converged immediately.
    """
    m = window
    N = q.shape[0]
    S = _seg_onehot(seg, n_seg, q.dtype)

    def step(carry, j):
        Q, q, q_prev, beta_prev = carry          # Q (m, N); beta_prev (n_seg,)
        w = op(q) - beta_prev[seg] * q_prev
        alpha = S @ (w * q)                      # (n_seg,)
        w = w - alpha[seg] * q
        # Full reorthogonalization against the window + constants (twice is
        # enough — Parlett), per problem: rows ≥ j of Q are zero so the
        # window mask is implicit.
        for _ in range(2):
            dots = (Q * w[None, :]) @ S.T        # (m, n_seg) per-problem Qᵀw
            w = w - (Q * dots[:, seg]).sum(0)
            w = _project_out_ones_seg(w, mask, seg, S)
        beta = jnp.sqrt(S @ (w * w))             # (n_seg,)
        bj = beta[seg]
        q_next = jnp.where(bj > 1e-12, w / jnp.maximum(bj, 1e-30), 0.0)
        Q = Q.at[j].set(q)
        return (Q, q_next, q, beta), (alpha, beta)

    Q0 = jnp.zeros((m, N), q.dtype)
    (Q, _, _, _), (alpha, beta) = jax.lax.scan(
        step,
        (Q0, q, jnp.zeros_like(q), jnp.zeros((n_seg,), q.dtype)),
        jnp.arange(m),
    )
    alpha_t, beta_t = alpha.T, beta.T            # (n_seg, m)

    def tridiag(a, b):
        return jnp.diag(a) + jnp.diag(b[:-1], 1) + jnp.diag(b[:-1], -1)

    T = jax.vmap(tridiag)(alpha_t, beta_t)
    evals, evecs = jnp.linalg.eigh(T)            # vmapped Ritz problems
    s = evecs[:, :, 0]                           # (n_seg, m)
    theta = evals[:, 0]                          # (n_seg,)
    y = (s.T[:, seg] * Q).sum(0)                 # per-problem Ritz vector
    ynorm = jnp.sqrt(S @ (y * y))
    y = y / jnp.maximum(ynorm, 1e-30)[seg]
    Ly = op(y)
    res = jnp.sqrt(S @ ((Ly - theta[seg] * y) ** 2))
    q_next = _project_out_ones_seg(y, mask, seg, S)
    qn = jnp.sqrt(S @ (q_next * q_next))
    q_next = q_next / jnp.maximum(qn, 1e-30)[seg]
    return y, theta, res, q_next


def lanczos_fiedler_batched(
    op,
    n: int,
    *,
    seg: jax.Array,
    n_seg: int,
    mask: jax.Array,
    b0: jax.Array,
    window: int = 30,
    max_restarts: int = 50,
    tol: float = 1e-3,
) -> tuple[jax.Array, BatchedLanczosInfo]:
    """All packed Fiedler solves in lockstep: (Y (N,), per-problem info).

    `op`: block-diagonal pytree operator over the packed (N,) slots (no
    cross-problem coupling).  `seg[j]` names slot j's subproblem id in
    [0, n_seg); `mask[j]` flags real (non-padding) slots.  An empty segment
    is a padding problem that converges on the first restart.  `b0` holds
    the packed start vectors (deterministic per-node seeds / warm starts).

    Everything outside `_packed_restart` runs on the host (NumPy): the
    start-vector projection, per-problem freezing, and convergence
    bookkeeping are cheap O(N) passes, and keeping them off the device
    means the ONLY compiled code on this path is the restart step itself.
    The obs span ``restarts`` covers all of it, and the counter
    ``restart_launches`` counts the `_packed_restart` launches.
    """
    with obs.timed("restarts"):
        seg_h = np.asarray(seg)
        mask_h = np.asarray(mask, dtype=np.float64)
        q_h = np.asarray(b0, dtype=np.float64)
        # Host analogue of _project_out_ones_seg + per-segment normalization.
        s = np.bincount(seg_h, weights=q_h * mask_h, minlength=n_seg)
        c = np.maximum(np.bincount(seg_h, weights=mask_h, minlength=n_seg), 1.0)
        q_h = (q_h - (s / c)[seg_h]) * mask_h
        nrm = np.sqrt(np.bincount(seg_h, weights=q_h * q_h, minlength=n_seg))
        q_h = q_h / np.maximum(nrm, 1e-30)[seg_h]
        q = jnp.asarray(q_h.astype(np.float32))

        y = q_h.astype(np.float32)
        theta = np.zeros(n_seg)
        res = np.full(n_seg, np.inf)
        done = np.zeros(n_seg, dtype=bool)
        breakdown = np.zeros(n_seg, dtype=bool)
        restarts = np.zeros(n_seg, dtype=np.int64)
        r = 0
        for r in range(1, max_restarts + 1):
            y_new, theta_new, res_new, q_next = _packed_restart(
                op, q, mask, seg, n_seg, window
            )
            theta_h, res_h = np.asarray(theta_new), np.asarray(res_new)
            finite = np.isfinite(theta_h) & np.isfinite(res_h)
            upd = ~done & finite  # a non-finite restart keeps the last state
            restarts[upd] = r
            theta = np.where(upd, theta_h, theta)
            res = np.where(upd, res_h, res)
            y = np.where(upd[seg_h], np.asarray(y_new), y)
            done |= res <= tol * np.maximum(theta, 1e-12)
            # Numerical breakdown: freeze the problem and flag it — its frozen
            # (θ, res) never met tolerance.
            breakdown |= ~finite & ~done
            done |= ~finite
            if done.all():
                break
            q = q_next
        obs.counter_add("restart_launches", r)

    info = BatchedLanczosInfo(
        restarts=restarts, eigenvalue=theta, residual=res, converged=done,
        breakdown=breakdown,
    )
    return y, info
