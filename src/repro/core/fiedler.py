"""Fiedler-vector solver facade: picks Lanczos or inverse iteration.

Adds the practical glue the RSB driver needs:
  * operator construction from a mesh (gather-scatter) or a graph (ELL),
  * power-of-two bucketing/padding so the recursion reuses compiled solvers
    (pad entries are fully decoupled: dummy gids / zero rows — the self-term
    cancellation makes `L` act as 0 on them),
  * a dense NumPy path for tiny subproblems (recursion tail),
  * optional geometric warm start (beyond-paper: seed with the coordinate
    along the dominant axis instead of noise — see EXPERIMENTS.md §Perf),
  * **multilevel (coarse-to-fine) warm starts** (`multilevel_warm_start`,
    on by default): a Galerkin hierarchy per subproblem (the `amg_setup`
    pairwise aggregation), a dense Fiedler solve on the coarsest graph, and
    a cascadic prolongation (one Jacobi-PCG inverse-iteration step per
    level, host NumPy) whose output seeds the device solve — the fine-level
    Lanczos then only *refines*, so callers can cap it at a few restarts,
  * **batched entry points** (`fiedler_from_graph_batched`,
    `fiedler_from_mesh_batched`): solve a whole RSB tree level at once.
    Subproblems are grouped into (n_pad, width_pad) **shape buckets**
    (power-of-two padded, batch padded to a power of two with fully-masked
    dummy rows), each bucket runs one vmapped solve whose compiled trace is
    shared by every bucket of the same shape for the life of the process.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.amg import amg_setup, amg_setup_batched, coarsen_graph
from repro.core.gather_scatter import GSHandle, GSLaplacian, _build
from repro.core.inverse_iteration import inverse_iteration, inverse_iteration_batched
from repro.core.lanczos import lanczos_fiedler, lanczos_fiedler_batched
from repro.core.laplacian import (
    EllLaplacian,
    dense_laplacian_np,
    ell_laplacian_batched,
    fill_ell_block as _fill_ell_block,
    spill_rows,
)
from repro.mesh.graphs import Graph, dual_graph_from_incidence

_DENSE_CUTOFF = 192


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1)).bit_length()


def ell_layout(degrees: np.ndarray, n_rows: int) -> tuple[int, int]:
    """(W, R): head width and overflow rows of a packed ELL Laplacian of
    `n_rows` rows over nodes of these degrees (`EllLaplacian`'s layout).

    The head is W = next_pow2(⌈mean degree⌉) slots wide, capped at the
    plain width next_pow2(largest degree); R = next_pow2 of the overflow
    rows its longer rows need.  The split is taken only where it gathers
    at most half the plain layout's slots, (n_rows + R)·W ≤ n_rows·plain/2;
    otherwise the plain layout (plain, 0), with no overflow at all."""
    deg = np.asarray(degrees, np.int64)
    plain = next_pow2(max(int(deg.max(initial=0)), 2))
    if not deg.size:
        return plain, 0
    width = min(next_pow2(max(int(np.ceil(deg.mean())), 2)), plain)
    need = int(spill_rows(deg, width).sum())
    rows = next_pow2(need) if need else 0
    if rows and 2 * (n_rows + rows) * width <= n_rows * plain:
        return width, rows
    return plain, 0


@dataclasses.dataclass
class FiedlerResult:
    vector: np.ndarray     # (n,) float — Fiedler components (real entries only)
    eigenvalue: float
    residual: float
    iterations: int        # restarts (lanczos) or outer iters (inverse)
    method: str
    levels: int = 0        # multilevel warm-start hierarchy depth (0 = none)
    breakdown: bool = False  # solver hit a non-finite iterate; stale (λ, res)


def _emit_fiedler_metrics(results) -> None:
    """Emit solver counters/gauges for completed solves into the active
    obs span (no-op outside a trace — counter_add early-outs)."""
    for r in results:
        if r is None:
            continue
        obs.counter_add("fiedler_solves")
        if r.method == "lanczos":
            obs.counter_add("lanczos_restarts", r.iterations)
        elif r.method == "inverse":
            obs.counter_add("inverse_outer_iters", r.iterations)
        obs.gauge_max("residual_max", float(r.residual))
        if r.levels:
            obs.gauge_max("multilevel_levels", r.levels)


# ---------------------------------------------------------------------------
# Multilevel (coarse-to-fine) warm starts — host NumPy, no compiled traces
# ---------------------------------------------------------------------------

def _lap_matvec_np(graph: Graph, deg: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Host Laplacian matvec L x = deg ⊙ x − A x over the COO view."""
    ax = np.bincount(
        graph.rows, weights=graph.weights * x[graph.indices], minlength=graph.n
    )
    return deg * x - ax


def _cg_refine_np(graph: Graph, deg: np.ndarray, inv_d: np.ndarray,
                  b: np.ndarray, iters: int) -> np.ndarray:
    """One cascadic inverse-iteration step: ≈solve L x = b with `iters`
    Jacobi-PCG steps, x₀ = b (host NumPy; every vector stays ⊥ 1)."""
    x = b.copy()
    r = b - _lap_matvec_np(graph, deg, x)
    r -= r.mean()
    z = inv_d * r
    z -= z.mean()
    p = z.copy()
    rz = r @ z
    for _ in range(iters):
        w = _lap_matvec_np(graph, deg, p)
        pw = p @ w
        if abs(pw) < 1e-30:
            break
        a = rz / pw
        x += a * p
        r -= a * w
        r -= r.mean()
        z = inv_d * r
        z -= z.mean()
        rz_new = r @ z
        if rz_new < 1e-30:
            break
        p = z + (rz_new / rz) * p
        rz = rz_new
    x -= x.mean()
    return x


def _rayleigh_ritz_pair_np(graph: Graph, deg: np.ndarray,
                           V: np.ndarray) -> np.ndarray | None:
    """Rayleigh–Ritz over span(V) (V: (n, k) candidates, k small): project
    out constants, orthonormalize, rotate to the L-eigenbasis of the
    subspace, columns sorted by ascending Ritz value.  None on breakdown."""
    V = V - V.mean(axis=0, keepdims=True)
    Q, _ = np.linalg.qr(V)
    W = np.stack([_lap_matvec_np(graph, deg, Q[:, j]) for j in range(Q.shape[1])], 1)
    G = Q.T @ W
    G = 0.5 * (G + G.T)
    if not np.isfinite(G).all():
        return None
    w, S = np.linalg.eigh(G)
    return Q @ S[:, np.argsort(w)]


def multilevel_warm_start(
    graph: Graph,
    *,
    coarse_cutoff: int = _DENSE_CUTOFF,
    refine_iters: int = 6,
) -> tuple[np.ndarray | None, int]:
    """Cascadic coarse-to-fine Fiedler warm start (returns (warm, n_levels)).

    Builds the same pairwise Galerkin hierarchy as `amg_setup` (consecutive
    nodes aggregate — callers feed RCB-ordered graphs, as the RSB engines
    do after the geometric pre-pass), solves the coarsest eigenproblem
    densely, then prolongs level by level with one Jacobi-PCG
    inverse-iteration step per candidate and level.

    A **block of two** candidates (y₂, y₃) rides the whole cascade with a
    per-level 2×2 Rayleigh–Ritz rotation: pairwise aggregation can shrink
    one graph axis faster than another, swapping the eigenvalue order
    between levels (a 24×28 grid coarsens toward 24×14, so the coarse
    Fiedler vector cuts the axis the FINE Fiedler vector does not) — a
    single-vector cascade would then hand the device solve an accurate
    approximation of the WRONG eigenvector, which satisfies the residual
    stopping test at λ₃.  Tracking the pair and re-sorting by fine-level
    Rayleigh quotient keeps the warm start on y₂.

    Everything runs on the host: the warm start adds NO compiled traces,
    and the device solve it seeds only needs a few refinement restarts
    (the RSB engines cap it at `fine_restarts`).  Returns (None, 0) for
    graphs at or below `coarse_cutoff` — those take the dense path
    outright — and on numerical breakdown (caller falls back to noise).
    """
    if graph.n <= coarse_cutoff:
        return None, 0
    levels: list[Graph] = [graph]
    aggs: list[np.ndarray] = []
    while levels[-1].n > coarse_cutoff:
        g = levels[-1]
        agg = np.arange(g.n, dtype=np.int64) // 2
        levels.append(coarsen_graph(g, agg, (g.n + 1) // 2))
        aggs.append(agg)
    w, v = np.linalg.eigh(dense_laplacian_np(levels[-1]))
    V = v[:, 1:3] if v.shape[1] >= 3 else v[:, 1:2]   # (n_c, ≤2) candidates
    for agg, g in zip(reversed(aggs), reversed(levels[:-1])):
        V = V[agg]                           # piecewise-constant prolongation
        deg = np.zeros(g.n)
        np.add.at(deg, g.rows, g.weights)
        inv_d = np.where(deg > 0, 1.0 / np.maximum(deg, 1e-30), 0.0)
        cols = []
        for j in range(V.shape[1]):
            c = V[:, j] - V[:, j].mean()
            nrm = np.linalg.norm(c)
            if not np.isfinite(nrm) or nrm < 1e-30:
                return None, 0               # degenerate level: fall back
            cols.append(_cg_refine_np(g, deg, inv_d, c / nrm, refine_iters))
        V = _rayleigh_ritz_pair_np(g, deg, np.stack(cols, 1))
        if V is None:
            return None, 0
    vec = V[:, 0]
    if not np.isfinite(vec).all():
        return None, 0
    return vec.astype(np.float32), len(aggs)


_INVERSE_NOISE_BLEND = 0.3


def _blend_noise(warm: np.ndarray, seed: int) -> np.ndarray:
    """Mix a deterministic noise floor into a multilevel warm start.

    Single-vector inverse iteration amplifies only the eigencomponents its
    start vector contains: a prolonged coarse Fiedler vector that lands
    (near-)orthogonal to y₂ — near-degenerate pairs, paper §9 — would trap
    the iteration on the wrong eigenvector.  Lanczos is immune (it builds a
    Krylov *subspace*), so only the inverse paths blend."""
    z = _noise_b0(seed, warm.shape[0])
    nw, nz = np.linalg.norm(warm), np.linalg.norm(z)
    if nw < 1e-30 or nz < 1e-30:
        return warm
    return (warm / nw + _INVERSE_NOISE_BLEND * z / nz).astype(np.float32)


def _graph_from_vert_gid(vert_gid: np.ndarray) -> Graph:
    """Assembled dual graph of one sub-mesh (compacted vertex id space)."""
    uniq, inv = np.unique(vert_gid, return_inverse=True)
    return dual_graph_from_incidence(
        inv.reshape(vert_gid.shape), uniq.size, vert_gid.shape[0]
    )


def _noise_b0(seed: int, n: int) -> np.ndarray:
    """Deterministic start-vector noise, generated on the host: identical
    between the unbatched and batched entry points (batch-of-one parity)
    and free of the threefry compile a first `jax.random.normal` costs."""
    return np.random.default_rng(seed).standard_normal(n).astype(np.float32)


def _gs_laplacian_from_np(gid: np.ndarray, n_global: int, n: int) -> GSLaplacian:
    """GSLaplacian with host-computed degrees (aw_apply(1) ≡ per-slot sum of
    gid multiplicities) — avoids `_build`'s eager JAX dispatch on the hot
    setup path.  gid: (n, K) or (B, n, K); per-problem id spaces for 3-D."""
    K = gid.shape[-1]
    if gid.ndim == 3:
        deg_full = np.stack([
            np.bincount(g.ravel(), minlength=n_global)[g].sum(-1) for g in gid
        ])
    else:
        deg_full = np.bincount(gid.ravel(), minlength=n_global)[gid].sum(-1)
    h = GSHandle(gid=jnp.asarray(gid.astype(np.int32)), n_global=n_global)
    return GSLaplacian(
        terms=((1.0, h),), n=n,
        degree_full=jnp.asarray(deg_full.astype(np.float32)),
        diag=jnp.asarray((deg_full - K).astype(np.float32)),
    )


def _fill_gs_block(vert_gid: np.ndarray, gid_block: np.ndarray,
                   base: int) -> int:
    """Compact one sub-mesh's gids into gid_block starting at id `base`;
    rows past E get one fresh singleton id per slot (no coupling,
    self-cancelling).  Returns the next unused id."""
    E, K = vert_gid.shape
    uniq, inv = np.unique(vert_gid, return_inverse=True)
    gid_block[:E] = inv.reshape(E, K) + base
    base += uniq.size
    n_rows = gid_block.shape[0]
    if n_rows > E:
        pad = (n_rows - E) * K
        gid_block[E:] = (base + np.arange(pad)).reshape(-1, K)
        base += pad
    return base


def _padded_gs_laplacian(vert_gid: np.ndarray, n_pad: int) -> GSLaplacian:
    """Gather-scatter Laplacian padded to n_pad elements (decoupled tail)."""
    gid = np.empty((n_pad, vert_gid.shape[1]), dtype=np.int64)
    ng = _fill_gs_block(vert_gid, gid, 0)
    h = GSHandle(gid=jnp.asarray(gid.astype(np.int32)), n_global=ng)
    return _build([(1.0, h)], n_pad)


def _padded_ell_laplacian(graph: Graph, n_pad: int, width_pad: int) -> EllLaplacian:
    C = np.tile(np.arange(n_pad, dtype=np.int64)[:, None], (1, width_pad))
    V = np.zeros((n_pad, width_pad), dtype=np.float64)
    D = np.zeros(n_pad, dtype=np.float64)
    _fill_ell_block(graph, C, V, D)
    return EllLaplacian(
        cols=jnp.asarray(C.astype(np.int32)),
        vals=jnp.asarray(V.astype(np.float32)),
        diag=jnp.asarray(D.astype(np.float32)),
        n=n_pad,
    )


def _dense_fiedler(L: np.ndarray) -> tuple[np.ndarray, float]:
    w, v = np.linalg.eigh(L)
    return v[:, 1], float(w[1])


def fiedler_from_graph(
    graph: Graph,
    *,
    method: str = "lanczos",
    order: np.ndarray | None = None,
    seed: int = 0,
    warm: np.ndarray | None = None,
    tol: float = 1e-3,
    window: int = 30,
    max_restarts: int = 50,
    pad: bool = True,
    use_kernel: bool = False,
    multilevel: bool = True,
) -> FiedlerResult:
    """Fiedler vector of an assembled graph Laplacian.

    `use_kernel=True` routes the ELL matvec through the Pallas `ell_spmv`
    kernel (interpret mode off-TPU).  `multilevel=True` (default) seeds the
    solve with a cascadic coarse-to-fine warm start (`multilevel_warm_start`)
    when no explicit `warm` vector is given — the iterative solve then only
    refines the prolonged coarse Fiedler vector.
    """
    n = graph.n
    if n <= _DENSE_CUTOFF:
        vec, lam = _dense_fiedler(dense_laplacian_np(graph))
        res = FiedlerResult(vec, lam, 0.0, 0, "dense")
        _emit_fiedler_metrics([res])
        return res

    ml_levels = 0
    if warm is None and multilevel:
        warm, ml_levels = multilevel_warm_start(graph)
        if warm is not None and method == "inverse":
            warm = _blend_noise(warm, seed)

    n_pad = next_pow2(n) if pad else n
    width = int(graph.degrees.max()) if graph.nnz else 1
    width_pad = next_pow2(max(width, 2)) if pad else width
    op = _padded_ell_laplacian(graph, n_pad, width_pad)
    if use_kernel:
        op = dataclasses.replace(op, use_kernel=True)
    mask = jnp.asarray((np.arange(n_pad) < n).astype(np.float32))
    if warm is not None:
        b0 = jnp.asarray(np.pad(warm.astype(np.float32), (0, n_pad - n)))
    else:
        b0 = jnp.asarray(_noise_b0(seed, n_pad))

    if method == "lanczos":
        # Pass the operator dataclass itself (a pytree): the window trace
        # is shared across same-shape operators instead of per instance.
        y, info = lanczos_fiedler(
            op, n_pad, mask=mask, key=jax.random.PRNGKey(seed), b0=b0,
            window=window, max_restarts=max_restarts, tol=tol,
        )
        iters = info.restarts
        lam, res = info.eigenvalue, info.residual
        broke = info.breakdown
    elif method == "inverse":
        pre = amg_setup(graph, order=order)
        ml_levels = max(ml_levels, len(pre.ops))
        obs.gauge_max("amg_levels", len(pre.ops))

        # AMG hierarchy is sized to the real graph; wrap to ignore padding.
        def precond(r):
            u = pre(r[:n])
            return jnp.pad(u, (0, n_pad - n))

        y, info = inverse_iteration(
            op.apply, n_pad, precond=precond, mask=mask,
            key=jax.random.PRNGKey(seed), b0=b0, tol=tol,
        )
        iters = info.outer_iters
        lam, res = info.eigenvalue, info.residual
        broke = info.breakdown
        obs.counter_add("cg_inner_iters", float(np.sum(info.inner_iters)))
    else:
        raise ValueError(f"unknown fiedler method: {method}")
    out = FiedlerResult(np.asarray(y[:n]), lam, res, iters, method,
                        levels=ml_levels, breakdown=broke)
    _emit_fiedler_metrics([out])
    return out


def fiedler_from_mesh(
    vert_gid: np.ndarray,
    *,
    method: str = "lanczos",
    graph_for_amg: Graph | None = None,
    order: np.ndarray | None = None,
    seed: int = 0,
    warm: np.ndarray | None = None,
    tol: float = 1e-3,
    window: int = 30,
    max_restarts: int = 50,
    pad: bool = True,
    multilevel: bool = True,
) -> FiedlerResult:
    """Fiedler vector via the matrix-free gather-scatter Laplacian (paper §5).

    `graph_for_amg` (the assembled dual graph) is only needed for
    method="inverse" — the AMG hierarchy requires assembled coarse levels
    (paper §7), while Lanczos runs fully matrix-free.  `multilevel=True`
    (default) assembles the dual graph on the host to build the cascadic
    coarse-to-fine warm start when no `warm` vector is given; the device
    solve itself stays matrix-free.
    """
    E = vert_gid.shape[0]
    if E <= _DENSE_CUTOFF:
        g = dual_graph_from_incidence(vert_gid, int(vert_gid.max()) + 1, E)
        vec, lam = _dense_fiedler(dense_laplacian_np(g))
        res = FiedlerResult(vec, lam, 0.0, 0, "dense")
        _emit_fiedler_metrics([res])
        return res

    ml_levels = 0
    if warm is None and multilevel:
        g_ml = graph_for_amg
        if g_ml is None:
            g_ml = _graph_from_vert_gid(np.asarray(vert_gid))
        warm, ml_levels = multilevel_warm_start(g_ml)
        if warm is not None and method == "inverse":
            warm = _blend_noise(warm, seed)

    n_pad = next_pow2(E) if pad else E
    op = _padded_gs_laplacian(vert_gid, n_pad)
    mask = jnp.asarray((np.arange(n_pad) < E).astype(np.float32))
    if warm is not None:
        b0 = jnp.asarray(np.pad(warm.astype(np.float32), (0, n_pad - E)))
    else:
        b0 = jnp.asarray(_noise_b0(seed, n_pad))

    if method == "lanczos":
        y, info = lanczos_fiedler(
            op, n_pad, mask=mask, key=jax.random.PRNGKey(seed), b0=b0,
            window=window, max_restarts=max_restarts, tol=tol,
        )
        iters, lam, res = info.restarts, info.eigenvalue, info.residual
        broke = info.breakdown
    elif method == "inverse":
        if graph_for_amg is None:
            raise ValueError("inverse iteration needs the assembled dual graph for AMG")
        pre = amg_setup(graph_for_amg, order=order)
        ml_levels = max(ml_levels, len(pre.ops))
        obs.gauge_max("amg_levels", len(pre.ops))

        def precond(r):
            u = pre(r[:E])
            return jnp.pad(u, (0, n_pad - E))

        y, info = inverse_iteration(
            op.apply, n_pad, precond=precond, mask=mask,
            key=jax.random.PRNGKey(seed), b0=b0, tol=tol,
        )
        iters, lam, res = info.outer_iters, info.eigenvalue, info.residual
        broke = info.breakdown
        obs.counter_add("cg_inner_iters", float(np.sum(info.inner_iters)))
    else:
        raise ValueError(f"unknown fiedler method: {method}")
    out = FiedlerResult(np.asarray(y[:E]), lam, res, iters, method,
                        levels=ml_levels, breakdown=broke)
    _emit_fiedler_metrics([out])
    return out


# ---------------------------------------------------------------------------
# Batched (level-synchronous) entry points
# ---------------------------------------------------------------------------

_padded_ell_laplacian_batched = ell_laplacian_batched


def _padded_gs_laplacian_batched(
    vert_gids: list, n_pad: int, b_pad: int
) -> GSLaplacian:
    """Stack B gather-scatter Laplacians into one (b_pad, n_pad, K) handle.

    Each subproblem's gids are compacted independently (per-problem id
    space); padded element slots get fresh singleton ids (decoupled,
    self-cancelling).  `n_global` is a shared power-of-two upper bound so
    every same-shape bucket reuses one compiled trace."""
    K = vert_gids[0].shape[1]
    gid = np.empty((b_pad, n_pad, K), dtype=np.int64)
    need = 2
    for b, vg in enumerate(vert_gids):
        need = max(need, _fill_gs_block(vg, gid[b], 0))
    ng = next_pow2(need)
    for b in range(len(vert_gids), b_pad):  # batch-padding dummy problems
        gid[b] = (np.arange(n_pad * K, dtype=np.int64) % ng).reshape(n_pad, K)
    return _gs_laplacian_from_np(gid, ng, n_pad)


def _batched_b0(sizes, seeds, warms, n_pad: int, b_pad: int) -> jax.Array:
    """Per-problem start vectors: padded warm starts where given, otherwise
    seeded noise; zero rows for batch-padding dummies."""
    rows = []
    for sz, sd, warm in zip(sizes, seeds, warms):
        if warm is not None:
            w = np.asarray(warm, dtype=np.float32)
            rows.append(np.pad(w, (0, n_pad - sz)))
        else:
            rows.append(_noise_b0(sd, n_pad))
    for _ in range(b_pad - len(rows)):
        rows.append(np.zeros(n_pad, dtype=np.float32))
    return jnp.asarray(np.stack(rows))


def _normalize_batch_args(B, seeds, warms):
    seeds = list(range(B)) if seeds is None else list(seeds)
    warms = [None] * B if warms is None else list(warms)
    if len(seeds) != B or len(warms) != B:
        raise ValueError("seeds/warms must match the batch length")
    return seeds, warms


# -- packed layout (one flat vector; the Lanczos single-trace fast path) ----

def _pack_layout(sizes, pack_slots=None, pack_segs=None):
    """Pack B subproblems into one flat vector of power-of-two blocks.

    Returns (offs, N, n_seg, seg, mask): problem b owns slots
    [offs[b], offs[b+1]) with its first sizes[b] slots real (mask 1).
    `pack_slots`/`pack_segs` pin N / n_seg to run-wide values so every tree
    level of an RSB run solves in ONE compiled trace (a level's subproblems
    partition the root set, so their padded blocks always fit the root's
    padded size); they are only overridden upward if a layout overflows.
    """
    pads = [next_pow2(max(s, 2)) for s in sizes]
    offs = np.concatenate([[0], np.cumsum(pads)]).astype(np.int64)
    total = int(offs[-1])
    N = next_pow2(total)
    if pack_slots is not None:
        N = max(N, int(pack_slots))
    n_seg = next_pow2(len(sizes))
    if pack_segs is not None:
        n_seg = max(n_seg, int(pack_segs))
    seg = np.zeros(N, dtype=np.int32)
    mask = np.zeros(N, dtype=np.float32)
    for b, s in enumerate(sizes):
        seg[offs[b]:offs[b + 1]] = b
        mask[offs[b]:offs[b] + s] = 1.0
    # trailing slots: seg 0, mask 0, zero operator rows — fully inert
    return offs, N, n_seg, seg, mask


def _packed_ell_laplacian(graphs: list, offs, N: int, width_pad: int,
                          overflow_rows: int = 0) -> EllLaplacian:
    """Block-diagonal ELL Laplacian over the packed slots (plain unbatched
    `EllLaplacian` of size N — each problem's cols are offset into its own
    block, so there is no cross-problem coupling), with a head `width_pad`
    wide and `overflow_rows` overflow rows for the hubs (`ell_layout`);
    overflow rows left unfilled are padding (owner = col = N − 1, val 0)."""
    C = np.tile(np.arange(N, dtype=np.int64)[:, None], (1, width_pad))
    V = np.zeros((N, width_pad), dtype=np.float64)
    D = np.zeros(N, dtype=np.float64)
    over = None
    if overflow_rows:
        over = (np.full((overflow_rows, width_pad), N - 1, dtype=np.int64),
                np.zeros((overflow_rows, width_pad), dtype=np.float64),
                np.full(overflow_rows, N - 1, dtype=np.int64))
    at = 0
    for b, g in enumerate(graphs):
        o, o_next = int(offs[b]), int(offs[b + 1])
        at = _fill_ell_block(g, C[o:o_next], V[o:o_next], D[o:o_next],
                             col_offset=o, over=over, at=at)
    spill = {}
    if over is not None:
        spill = dict(over_cols=jnp.asarray(over[0].astype(np.int32)),
                     over_vals=jnp.asarray(over[1].astype(np.float32)),
                     over_owner=jnp.asarray(over[2].astype(np.int32)))
    return EllLaplacian(
        cols=jnp.asarray(C.astype(np.int32)),
        vals=jnp.asarray(V.astype(np.float32)),
        diag=jnp.asarray(D.astype(np.float32)),
        n=N,
        **spill,
    )


def _packed_gs_laplacian(vert_gids: list, offs, N: int) -> GSLaplacian:
    """Block-diagonal gather-scatter Laplacian over the packed slots: each
    problem's compacted gids live in a disjoint range of one shared id
    space; padding slots get fresh singleton ids (self-cancelling).
    `n_global` is the shape-stable bound next_pow2(N·K)."""
    K = vert_gids[0].shape[1]
    gid = np.empty((N, K), dtype=np.int64)
    base = 0
    for b, vg in enumerate(vert_gids):
        o, o_next = int(offs[b]), int(offs[b + 1])
        base = _fill_gs_block(vg, gid[o:o_next], base)
    tail = int(offs[-1])
    if N > tail:
        gid[tail:] = (base + np.arange((N - tail) * K)).reshape(-1, K)
    return _gs_laplacian_from_np(gid, next_pow2(N * K), N)


def _packed_b0(sizes, offs, N: int, seeds, warms) -> jax.Array:
    out = np.zeros(N, dtype=np.float32)
    for b, s in enumerate(sizes):
        o, o_next = int(offs[b]), int(offs[b + 1])
        if warms[b] is not None:
            out[o:o + s] = np.asarray(warms[b], dtype=np.float32)
        else:
            out[o:o_next] = _noise_b0(seeds[b], o_next - o)
    return jnp.asarray(out)


def _solve_inverse_buckets(results, solve_ix, size_of, bucket_key, build_op,
                           seeds, warms, tol, *, graph_of=None,
                           precond="jacobi"):
    """Shared method="inverse" tail for both batched entry points: group
    problems into shape buckets, run the leading-batch-dim preconditioned
    solve per bucket, unpack FiedlerResults in place.

    precond="jacobi" builds the preconditioner from each operator's own
    diagonal; precond="amg" builds one packed `BatchedAMG` V-cycle per
    bucket from the assembled graphs (`graph_of(i)` must be given — the
    graph path hands over the input graphs, the mesh path assembles each
    sub-mesh's dual graph on the host, exactly like the unbatched path's
    `graph_for_amg`)."""
    if precond not in ("jacobi", "amg"):
        raise ValueError(f"unknown preconditioner: {precond}")
    if precond == "amg" and graph_of is None:
        raise ValueError("precond='amg' needs assembled graphs")
    buckets: dict = {}
    for i in solve_ix:
        buckets.setdefault(bucket_key(i), []).append(i)
    for key, ix in sorted(buckets.items()):
        n_pad = key[0]
        b_pad = next_pow2(len(ix))
        op = build_op(ix, key, b_pad)
        pre = None
        pre_levels = 0
        if precond == "amg":
            pre = amg_setup_batched([graph_of(i) for i in ix], n_pad, b_pad)
            pre_levels = len(pre.ops)
            obs.gauge_max("amg_levels", pre_levels)
        mask = np.zeros((b_pad, n_pad), dtype=np.float32)
        for r, i in enumerate(ix):
            mask[r, : size_of(i)] = 1.0
        b0 = _batched_b0(
            [size_of(i) for i in ix], [seeds[i] for i in ix],
            [warms[i] for i in ix], n_pad, b_pad,
        )
        Y, info = inverse_iteration_batched(
            op, n_pad, mask=jnp.asarray(mask), b0=b0, tol=tol, precond=pre
        )
        obs.counter_add(
            "cg_inner_iters",
            float(sum(np.asarray(c).sum() for c in info.inner_iters)))
        Yh = np.asarray(Y)
        for r, i in enumerate(ix):
            results[i] = FiedlerResult(
                Yh[r, : size_of(i)], float(info.eigenvalue[r]),
                float(info.residual[r]), int(info.outer_iters[r]), "inverse",
                levels=pre_levels,
                breakdown=bool(info.breakdown[r])
                if info.breakdown is not None else False,
            )


def _solve_packed_lanczos(op, offs, N, n_seg, seg, mask, b0, sizes,
                          tol, window, max_restarts):
    Y, info = lanczos_fiedler_batched(
        op, N, seg=seg, n_seg=n_seg, mask=mask,
        b0=b0, window=window, max_restarts=max_restarts, tol=tol,
    )
    Yh = np.asarray(Y)
    return [
        FiedlerResult(
            Yh[int(offs[b]):int(offs[b]) + s], float(info.eigenvalue[b]),
            float(info.residual[b]), int(info.restarts[b]), "lanczos",
            breakdown=bool(info.breakdown[b])
            if info.breakdown is not None else False,
        )
        for b, s in enumerate(sizes)
    ]


def fiedler_from_graph_batched(
    graphs: list,
    *,
    method: str = "lanczos",
    seeds: list | None = None,
    warms: list | None = None,
    tol: float = 1e-3,
    window: int = 30,
    max_restarts: int = 50,
    pack_slots: int | None = None,
    pack_segs: int | None = None,
    width_pad: int | None = None,
    overflow_rows: int | None = None,
    use_kernel: bool = False,
    multilevel: bool = True,
    precond: str = "jacobi",
) -> list:
    """Fiedler vectors of B independent graphs in one batched solve.

    Returns FiedlerResults aligned with the input order; problems at or
    below the dense cutoff take the same dense path as the unbatched entry
    point (exact parity on a batch of one).

    method="lanczos" packs all subproblems into one flat block-diagonal
    solve whose trace is keyed by (pack_slots, pack_segs, width_pad,
    overflow_rows, window) — the RSB engine pins those to run-wide values
    so one trace serves the whole run.  The ELL layout (head width,
    overflow rows: `ell_layout`) is this call's own where `width_pad` is
    not given; a pinned layout keeps its width, and its overflow rows are
    raised to the next power of two where this call's hubs need more.
    method="inverse" runs batched flexcg over leading-batch-dim operators
    bucketed by (n_pad, width_pad), with `precond="jacobi"` (the
    operator's own diagonal) or `precond="amg"` (one packed `BatchedAMG`
    V-cycle per bucket — paper §7's preconditioner, batched).  `use_kernel=True` routes BOTH layouts
    through the Pallas `ell_spmv` kernel: the packed 2-D operator uses the
    flat kernel and the 3-D leading-batch-dim operators use the batched
    grid variant.  `multilevel=True` (default) fills every missing `warms`
    entry with the cascadic coarse-to-fine warm start of
    :func:`multilevel_warm_start`.
    """
    B = len(graphs)
    seeds, warms = _normalize_batch_args(B, seeds, warms)
    results: list = [None] * B
    solve_ix = []
    # The host's work ahead of the device: dense solves of the small
    # problems and the multilevel warm starts of the others.
    with obs.timed("warm_start"):
        for i, g in enumerate(graphs):
            if g.n <= _DENSE_CUTOFF:
                vec, lam = _dense_fiedler(dense_laplacian_np(g))
                results[i] = FiedlerResult(vec, lam, 0.0, 0, "dense")
            else:
                solve_ix.append(i)
        ml_levels = {i: 0 for i in solve_ix}
        if multilevel:
            for i in solve_ix:
                if warms[i] is None:
                    warms[i], ml_levels[i] = multilevel_warm_start(graphs[i])
                    if warms[i] is not None and method == "inverse":
                        warms[i] = _blend_noise(warms[i], seeds[i])
    if not solve_ix:
        _emit_fiedler_metrics(results)
        return results

    if method == "lanczos":
        with obs.timed("pack") as t_pack:
            sizes = [graphs[i].n for i in solve_ix]
            offs, N, n_seg, seg, mask = _pack_layout(sizes, pack_slots,
                                                     pack_segs)
            solved = [graphs[i] for i in solve_ix]
            deg = np.concatenate([g.degrees for g in solved])
            if width_pad is None:
                width, rows = ell_layout(deg, N)
            else:
                width, rows = int(width_pad), int(overflow_rows or 0)
            spilled = int(spill_rows(deg, width).sum())
            if spilled > rows:
                rows = next_pow2(spilled)
            op = _packed_ell_laplacian(solved, offs, N, width, rows)
            if use_kernel:
                op = dataclasses.replace(op, use_kernel=True)
            b0 = _packed_b0(sizes, offs, N, [seeds[i] for i in solve_ix],
                            [warms[i] for i in solve_ix])
            seg, mask = jnp.asarray(seg), jnp.asarray(mask)
        packed = _solve_packed_lanczos(
            op, offs, N, n_seg, seg, mask, b0, sizes, tol, window, max_restarts
        )
        # Every restart launch gathers over all (N + rows) × width slots
        # once per Lanczos step: count the slots, the real entries among
        # them and the overflow rows the hubs filled.
        launches = max(r.iterations for r in packed)
        if isinstance(t_pack, obs.Span):
            for name, v in (("ell_slots", (N + rows) * width),
                            ("ell_nnz", sum(g.nnz for g in solved)),
                            ("ell_overflow_rows", spilled)):
                t_pack.counters[name] = (t_pack.counters.get(name, 0.0)
                                         + float(v * launches))
        for r, i in enumerate(solve_ix):
            results[i] = packed[r]
            results[i].levels = ml_levels[i]
        _emit_fiedler_metrics(results)
        return results

    if method != "inverse":
        raise ValueError(f"unknown fiedler method: {method}")

    def bucket_key(i):
        g = graphs[i]
        width = int(g.degrees.max()) if g.nnz else 1
        return (next_pow2(g.n), next_pow2(max(width, 2)))

    def build_op(ix, key, b_pad):
        op = _padded_ell_laplacian_batched(
            [graphs[i] for i in ix], key[0], key[1], b_pad
        )
        if use_kernel:
            op = dataclasses.replace(op, use_kernel=True)
        return op

    _solve_inverse_buckets(
        results, solve_ix, lambda i: graphs[i].n, bucket_key, build_op,
        seeds, warms, tol, graph_of=lambda i: graphs[i], precond=precond,
    )
    for i in solve_ix:  # deepest hierarchy used: warm start or AMG ladder
        results[i].levels = max(results[i].levels, ml_levels[i])
    _emit_fiedler_metrics(results)
    return results


def fiedler_from_mesh_batched(
    vert_gids: list,
    *,
    method: str = "lanczos",
    seeds: list | None = None,
    warms: list | None = None,
    tol: float = 1e-3,
    window: int = 30,
    max_restarts: int = 50,
    pack_slots: int | None = None,
    pack_segs: int | None = None,
    multilevel: bool = True,
    precond: str = "jacobi",
    graphs: list | None = None,
) -> list:
    """Matrix-free batched analogue of :func:`fiedler_from_mesh`: B element
    sub-meshes (their (E, K) global-id tables) per call.  method="lanczos"
    packs every sub-mesh into one flat gather-scatter solve (one trace per
    run when pack_slots/pack_segs are pinned); method="inverse" uses the
    leading-batch-dim path with `precond="jacobi"` or `precond="amg"` (a
    packed `BatchedAMG` V-cycle over the assembled dual graphs — the fine
    operator stays matrix-free gather-scatter, exactly like the unbatched
    path's `graph_for_amg`).  `multilevel=True` (default) fills missing
    `warms` entries with the cascadic coarse-to-fine warm start.

    `graphs` optionally supplies each sub-mesh's assembled dual graph (the
    batched `graph_for_amg` analogue): the RSB mesh engine extracts all of
    a level's subgraphs in one vectorized pass, which is much cheaper than
    re-assembling every problem here from its gid table.  Entries may be
    None; anything missing is assembled on demand."""
    B = len(vert_gids)
    seeds, warms = _normalize_batch_args(B, seeds, warms)
    graphs = [None] * B if graphs is None else list(graphs)
    if len(graphs) != B:
        raise ValueError("graphs must match the batch length")

    def graph_of(i):
        if graphs[i] is None:
            graphs[i] = _graph_from_vert_gid(np.asarray(vert_gids[i]))
        return graphs[i]

    results: list = [None] * B
    solve_ix = []
    for i, vg in enumerate(vert_gids):
        if vg.shape[0] <= _DENSE_CUTOFF:
            vec, lam = _dense_fiedler(dense_laplacian_np(graph_of(i)))
            results[i] = FiedlerResult(vec, lam, 0.0, 0, "dense")
        else:
            solve_ix.append(i)
    if not solve_ix:
        _emit_fiedler_metrics(results)
        return results

    ml_levels = {i: 0 for i in solve_ix}

    if multilevel:
        for i in solve_ix:
            if warms[i] is None:
                warms[i], ml_levels[i] = multilevel_warm_start(graph_of(i))
                if warms[i] is not None and method == "inverse":
                    warms[i] = _blend_noise(warms[i], seeds[i])

    if method == "lanczos":
        sizes = [vert_gids[i].shape[0] for i in solve_ix]
        offs, N, n_seg, seg, mask = _pack_layout(sizes, pack_slots, pack_segs)
        op = _packed_gs_laplacian([vert_gids[i] for i in solve_ix], offs, N)
        b0 = _packed_b0(sizes, offs, N, [seeds[i] for i in solve_ix],
                        [warms[i] for i in solve_ix])
        packed = _solve_packed_lanczos(
            op, offs, N, n_seg, jnp.asarray(seg), jnp.asarray(mask), b0,
            sizes, tol, window, max_restarts
        )
        for r, i in enumerate(solve_ix):
            results[i] = packed[r]
            results[i].levels = ml_levels[i]
        _emit_fiedler_metrics(results)
        return results

    if method != "inverse":
        raise ValueError(f"unknown fiedler method: {method}")
    _solve_inverse_buckets(
        results, solve_ix, lambda i: vert_gids[i].shape[0],
        lambda i: (next_pow2(vert_gids[i].shape[0]),),
        lambda ix, key, b_pad: _padded_gs_laplacian_batched(
            [vert_gids[i] for i in ix], key[0], b_pad
        ),
        seeds, warms, tol, graph_of=graph_of, precond=precond,
    )
    for i in solve_ix:  # deepest hierarchy used: warm start or AMG ladder
        results[i].levels = max(results[i].levels, ml_levels[i])
    _emit_fiedler_metrics(results)
    return results


# ---------------------------------------------------------------------------
# Degenerate Fiedler pairs (paper §9 future work, implemented here)
# ---------------------------------------------------------------------------

def fiedler_pair_from_graph(
    graph: Graph,
    *,
    seed: int = 0,
    tol: float = 1e-4,
    window: int = 40,
    max_restarts: int = 60,
) -> tuple[np.ndarray, np.ndarray, float, float]:
    """(y₂, y₃, λ₂, λ₃): the two smallest nontrivial eigenpairs.

    Paper §9: on topologically-checkerboard graphs λ₂ has multiplicity 2
    and single-vector Lanczos returns an arbitrary member of the eigenspace
    whose cut quality varies (45° cuts expose ≈2N faces vs N).  We find the
    second vector by SPECTRAL DEFLATION: run Lanczos again on
    `L' = L + σ·y₂y₂ᵀ` (σ > λ_max pushes y₂'s eigenvalue out of the way),
    which needs no changes to the Lanczos kernel itself.
    """
    res1 = fiedler_from_graph(graph, method="lanczos", seed=seed, tol=tol,
                              window=window, max_restarts=max_restarts)
    y1 = res1.vector / max(np.linalg.norm(res1.vector), 1e-30)

    n = graph.n
    n_pad = next_pow2(n)
    width = int(graph.degrees.max()) if graph.nnz else 1
    op = _padded_ell_laplacian(graph, n_pad, next_pow2(max(width, 2)))
    mask = jnp.asarray((np.arange(n_pad) < n).astype(np.float32))
    y1p = jnp.asarray(np.pad(y1.astype(np.float32), (0, n_pad - n)))
    # Gershgorin bound on λ_max; σ above it exiles y₂'s eigenvalue
    sigma = 4.0 * float(np.max(np.asarray(op.diag))) + 1.0

    def deflated(x):
        return op.apply(x) + sigma * y1p * jnp.vdot(y1p, x)

    y, info = lanczos_fiedler(
        deflated, n_pad, mask=mask, key=jax.random.PRNGKey(seed + 1),
        window=window, max_restarts=max_restarts, tol=tol,
    )
    y2 = np.asarray(y[:n])
    y2 = y2 - y1 * float(y1 @ y2)          # exact orthogonality polish
    y2 /= max(np.linalg.norm(y2), 1e-30)
    return y1, y2, res1.eigenvalue, info.eigenvalue


def best_cut_in_pair(
    graph: Graph,
    y1: np.ndarray,
    y2: np.ndarray,
    *,
    n_theta: int = 16,
    weights: np.ndarray | None = None,
) -> tuple[np.ndarray, float, float]:
    """Paper §9: sweep θ over span{y₂, y₃} and keep the balanced bisection
    with the minimum ω-cut.  Returns (fiedler-like vector, θ, cut)."""
    w = np.ones(graph.n) if weights is None else np.asarray(weights, np.float64)
    rows, cols, ew = graph.rows, graph.indices, graph.weights
    best = (None, 0.0, np.inf)
    for theta in np.linspace(0.0, np.pi, n_theta, endpoint=False):
        v = np.cos(theta) * y1 + np.sin(theta) * y2
        order = np.argsort(v, kind="stable")
        half = np.zeros(graph.n, dtype=bool)
        cw = np.cumsum(w[order])
        k = int(np.searchsorted(cw - w[order] / 2, cw[-1] / 2)) + 1
        half[order[:k]] = True
        cut = float(ew[half[rows] != half[cols]].sum() / 2.0)
        if cut < best[2]:
            best = (v, float(theta), cut)
    return best
