"""Plain reference for the partition cells: dual graph, checker, λ₂, RSB.

Nothing here imports the program under test.  The semantics follow the
parRSB paper: two hex elements are adjacent when they share a vertex, and
the edge weight ω is the number of vertices they share (1, 2 or 4).  A
graph input (:func:`graph_from_edges`) is its own adjacency, every edge of
weight 1.  A partition is sound when every label lies in ``[0, nparts)``,
every part is non-empty and connected in the graph, and every part's
weight lies inside ``(1 ± balance_tol)`` of the mean.  Its cut is the
ω-weighted count of edges whose ends lie in different parts, compared with
the cut of plain recursive coordinate bisection (:func:`rcb_labels`) where
the input has coordinates, and with that of plain float64 recursive
spectral bisection (:func:`rsb_labels`) for a graph input.

The Fiedler reference is λ₂ of the Laplacian ``L = D − A`` in float64
(ARPACK through SciPy for a mesh, :func:`fiedler_pair` for a graph, or a
dense solve when small), of the whole input and of each subgraph that a
node of the bisection tree induces.  :func:`lanczos_lambda2` is a plain
windowed Lanczos in JAX that takes a dtype: at bfloat16 it is the control
that the λ₂ comparison has to fail.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import warnings

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csgraph
import scipy.sparse.linalg as sla


@dataclasses.dataclass(frozen=True)
class DualGraph:
    """Symmetric CSR adjacency: both (i, j) and (j, i) are stored."""

    adj: sp.csr_matrix     # (n, n) float64, ω on the off-diagonal

    @property
    def n(self) -> int:
        return self.adj.shape[0]

    @property
    def nnz(self) -> int:
        return int(self.adj.nnz)

    def laplacian(self) -> sp.csr_matrix:
        deg = np.asarray(self.adj.sum(axis=1)).ravel()
        return (sp.diags(deg) - self.adj).tocsr()


def dual_graph(vert_gid: np.ndarray) -> DualGraph:
    """Vertex-sharing dual graph of an (E, 8) corner-id table."""
    vert_gid = np.asarray(vert_gid)
    E, K = vert_gid.shape
    _, verts = np.unique(vert_gid.ravel(), return_inverse=True)
    elems = np.repeat(np.arange(E), K)
    inc = sp.csr_matrix((np.ones(E * K), (elems, verts.ravel())),
                        shape=(E, int(verts.max()) + 1))
    shared = (inc @ inc.T).tocsr()          # (E, E): shared-vertex counts
    shared.setdiag(0)
    shared.eliminate_zeros()
    return DualGraph(adj=shared)


def graph_from_edges(n: int, src: np.ndarray, dst: np.ndarray) -> DualGraph:
    """The graph of an undirected edge list over ``n`` nodes: an edge in
    either direction, or in both, or listed more than once, is one edge of
    weight 1; self-loops are dropped; each row's columns are sorted."""
    src = np.asarray(src, np.int64).ravel()
    dst = np.asarray(dst, np.int64).ravel()
    keep = src != dst
    src, dst = src[keep], dst[keep]
    adj = sp.csr_matrix((np.ones(2 * src.size), (np.r_[src, dst],
                                                 np.r_[dst, src])),
                        shape=(n, n))
    adj.sum_duplicates()
    adj.data[:] = 1.0
    return DualGraph(adj=adj)


def edge_cut(g: DualGraph, labels: np.ndarray) -> float:
    """ω-weighted cut, each undirected edge counted once."""
    coo = g.adj.tocoo()
    cut = labels[coo.row] != labels[coo.col]
    return float(coo.data[cut].sum() / 2.0)


def check_partition(g: DualGraph, labels: np.ndarray, weights: np.ndarray,
                    nparts: int, balance_tol: float) -> dict:
    """Every plain check of one partition.  Counts are exact (limit 0);
    ``balance`` is the largest relative distance of a part's weight from
    the mean; ``cut`` is the ω-weighted cut."""
    labels = np.asarray(labels)
    out = {"out_of_range": int(((labels < 0) | (labels >= nparts)).sum()),
           "empty_parts": nparts, "disconnected_parts": nparts,
           "balance": float("inf"), "cut": float("nan")}
    if labels.shape != (g.n,) or out["out_of_range"]:
        out["out_of_range"] = max(out["out_of_range"], 1)
        return out
    labels = labels.astype(np.int64)
    pw = np.bincount(labels, weights=weights, minlength=nparts)
    mean = float(weights.sum()) / nparts
    out["empty_parts"] = int((np.bincount(labels, minlength=nparts) == 0).sum())
    out["balance"] = float(np.abs(pw / mean - 1.0).max())
    coo = g.adj.tocoo()
    same = labels[coo.row] == labels[coo.col]
    inside = sp.csr_matrix((np.ones(int(same.sum())),
                            (coo.row[same], coo.col[same])), shape=g.adj.shape)
    _, comp = csgraph.connected_components(inside, directed=False)
    # A part with k > 1 components contributes k − 1 surplus components.
    pairs = np.unique(np.stack([labels, comp], axis=1), axis=0)
    per_part = np.bincount(pairs[:, 0], minlength=nparts)
    out["disconnected_parts"] = int((per_part > 1).sum())
    out["cut"] = float(coo.data[~same].sum() / 2.0)
    return out


DENSE_N = 600      # below this a dense solve is quicker than ARPACK


def lambda2_reference(g: DualGraph) -> float:
    """λ₂ of the dual-graph Laplacian in float64: the second smallest
    eigenvalue, 0 where the graph is disconnected."""
    if g.n <= DENSE_N:
        return float(np.linalg.eigvalsh(g.laplacian().toarray())[1])
    vals = sla.eigsh(g.laplacian(), k=3, which="SA", tol=1e-12, ncv=60,
                     maxiter=100_000, return_eigenvectors=False)
    vals = np.sort(vals)
    return float(vals[1])


def subgraph(g: DualGraph, idx: np.ndarray) -> DualGraph:
    """The subgraph that the elements ``idx`` induce."""
    return DualGraph(adj=g.adj[idx][:, idx].tocsr())


class NodeLambda2:
    """λ₂ of the subgraphs of one graph, each solved once and kept by its
    element set, by ``solve`` (:func:`lambda2_reference`, or for a graph
    input with hubs the λ₂ of :func:`fiedler_pair`)."""

    def __init__(self, g: DualGraph, solve=None):
        self.g, self._seen = g, {}
        self.solve = solve or lambda2_reference

    def __call__(self, idx: np.ndarray) -> tuple[float, float]:
        """``(λ₂, scale)`` of the subgraph ``idx`` induces.  The scale is
        λ₂ itself where the subgraph is connected; where it is not, λ₂ is
        0, and the scale is the subgraph's mean weighted degree."""
        key = hashlib.blake2b(np.sort(idx).tobytes(), digest_size=16).digest()
        if key not in self._seen:
            sub = subgraph(self.g, idx)
            ncomp, _ = csgraph.connected_components(sub.adj, directed=False)
            if ncomp == 1:
                lam = self.solve(sub)
                self._seen[key] = (lam, lam)
            else:
                self._seen[key] = (0.0, float(sub.adj.sum()) / sub.n)
        return self._seen[key]

    def rel_err(self, value: float, idx: np.ndarray) -> float:
        lam, scale = self(idx)
        return abs(value - lam) / scale

    def top(self) -> float:
        return self(np.arange(self.g.n))[0]


def tree_nodes(raw: np.ndarray, nparts: int) -> list:
    """The nodes of the bisection tree that the raw labels ``raw`` (before
    any post stage) imply, level by level and in part order:
    ``(level, parts, elements)``.  A node holds the parts ``[lo, hi)``;
    its halves hold ``[lo, lo + (hi − lo) // 2)`` and the rest.  A node of
    one part or one element is a leaf and has no solve."""
    raw = np.asarray(raw)
    out, level = [], 0
    active = [(0, nparts, np.arange(raw.size))]
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


def rcb_labels(coords: np.ndarray, weights: np.ndarray,
               nparts: int) -> np.ndarray:
    """Plain recursive coordinate bisection: split the longest axis at the
    weighted point that gives the halves ⌊p/2⌋ and ⌈p/2⌉ of the weight."""
    labels = np.zeros(coords.shape[0], dtype=np.int64)

    def rec(idx, lo, hi):
        p = hi - lo
        if p <= 1 or idx.size <= 1:
            labels[idx] = lo
            return
        c = coords[idx]
        ax = int(np.argmax(c.max(0) - c.min(0)))
        order = idx[np.argsort(c[:, ax], kind="stable")]
        cw = np.cumsum(weights[order])
        k = int(np.searchsorted(cw, cw[-1] * (p // 2) / p)) + 1
        k = min(max(k, 1), idx.size - 1)
        rec(order[:k], lo, lo + p // 2)
        rec(order[k:], lo + p // 2, hi)

    rec(np.arange(coords.shape[0]), 0, nparts)
    return labels


def fiedler_pair(g: DualGraph) -> tuple[float, np.ndarray]:
    """λ₂ of a connected graph's Laplacian in float64, and its
    eigenvector, with the vector's sign fixed so that its entry of largest
    magnitude is positive.  Dense up to ``DENSE_N`` nodes.  Above, LOBPCG
    for the two lowest eigenpairs orthogonal to the constants, with the
    inverse degrees as preconditioner: on a graph with hubs, ARPACK's
    Lanczos needs many restarts, since λ_max, about the largest degree,
    dwarfs the gap above λ₂.  Where LOBPCG leaves a residual
    ``‖Lv − λ₂v‖`` above ``1e-6 · λ₂``, ARPACK solves it instead."""
    if g.n <= DENSE_N:
        vals, vecs = np.linalg.eigh(g.laplacian().toarray())
        lam, v = float(vals[1]), vecs[:, 1]
    else:
        L = g.laplacian()
        x0 = np.random.default_rng(0).standard_normal((g.n, 2))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", UserWarning)
            vals, vecs = sla.lobpcg(
                L, x0, M=sp.diags(1.0 / L.diagonal()),
                Y=np.ones((g.n, 1)), largest=False, tol=1e-8, maxiter=3000)
        lam, v = float(vals[np.argmin(vals)]), vecs[:, np.argmin(vals)]
        v = v / np.linalg.norm(v)
        if not np.linalg.norm(L @ v - lam * v) <= 1e-6 * lam:
            vals, vecs = sla.eigsh(L, k=3, which="SA", tol=1e-12, ncv=60,
                                   maxiter=100_000, v0=x0[:, 0])
            i = np.argsort(vals)[1]
            lam, v = float(vals[i]), vecs[:, i]
    return lam, (v if v[np.argmax(np.abs(v))] > 0 else -v)


def spectral_order(g: DualGraph) -> np.ndarray:
    """The nodes of ``g`` in the order that its bisection splits.  A
    connected graph is ordered by its Fiedler vector.  A disconnected one
    is ordered by (component, Fiedler value within the component): the
    components in the order of SciPy's labels (the component of node 0
    first, then that of the lowest node not yet labelled, and so on),
    each by the Fiedler vector of its own subgraph, a node alone by 0."""
    ncomp, comp = csgraph.connected_components(g.adj, directed=False)
    if ncomp == 1:
        return np.argsort(fiedler_pair(g)[1], kind="stable")
    value = np.zeros(g.n)
    for c in range(ncomp):
        idx = np.flatnonzero(comp == c)
        if idx.size > 1:
            value[idx] = fiedler_pair(subgraph(g, idx))[1]
    return np.lexsort((value, comp))


def rsb_labels(g: DualGraph, weights: np.ndarray,
               nparts: int) -> np.ndarray:
    """Plain recursive spectral bisection in float64: order each tree
    node's subgraph by :func:`spectral_order` and split it at the weighted
    point that gives the halves ⌊p/2⌋ and ⌈p/2⌉ of the weight."""
    labels = np.zeros(g.n, dtype=np.int64)

    def rec(idx, lo, hi):
        p = hi - lo
        if p <= 1 or idx.size <= 1:
            labels[idx] = lo
            return
        order = idx[spectral_order(subgraph(g, idx))]
        cw = np.cumsum(weights[order])
        k = int(np.searchsorted(cw, cw[-1] * (p // 2) / p)) + 1
        k = min(max(k, 1), idx.size - 1)
        rec(order[:k], lo, lo + p // 2)
        rec(order[k:], lo + p // 2, hi)

    rec(np.arange(g.n), 0, nparts)
    return labels


def ell_arrays(g: DualGraph, n_pad: int = 0,
               width_pad: int = 0) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(cols, vals, deg) of the Laplacian in padded ELL form, ``n_pad``
    rows and ``width_pad`` columns at least; padding entries point at
    their own row with weight 0, and padding rows have degree 0."""
    adj = g.adj
    deg_count = np.diff(adj.indptr)
    width = max(int(deg_count.max()), 1, width_pad)
    n = max(g.n, n_pad)
    rows = np.repeat(np.arange(g.n), deg_count)
    pos = np.arange(adj.nnz) - adj.indptr[rows]
    cols = np.tile(np.arange(n)[:, None], (1, width))
    vals = np.zeros((n, width))
    deg = np.zeros(n)
    cols[rows, pos] = adj.indices
    vals[rows, pos] = adj.data
    deg[:g.n] = np.asarray(adj.sum(axis=1)).ravel()
    return cols, vals, deg


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length()


@functools.lru_cache(maxsize=None)
def _lanczos_program(steps: int):
    """The jitted window of :func:`lanczos_lambda2`: (α, β) of ``steps``
    Lanczos steps in the dtype of its inputs.  Rows where ``mask`` is 0
    are padding: they start at 0 and stay there."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(cols, vals, deg, mask, q0):
        dtype = q0.dtype
        n_real = jnp.sum(mask)

        def op(x):
            return deg * x - (vals * x[cols]).sum(-1)

        def step(j, carry):
            Q, q, q_prev, b_prev, alpha, beta = carry
            w = op(q) - b_prev * q_prev
            a = jnp.vdot(w, q)
            w = w - a * q
            Q = Q.at[j].set(q)
            for _ in range(2):
                w = w - Q.T @ (Q @ w)
                w = w - mask * (jnp.sum(w) / n_real)
            b = jnp.sqrt(jnp.vdot(w, w))
            q_next = w / jnp.maximum(b, jnp.asarray(1e-30, dtype))
            return (Q, q_next, q, b, alpha.at[j].set(a), beta.at[j].set(b))

        n = q0.shape[0]
        z = jnp.zeros((), dtype)
        init = (jnp.zeros((steps, n), dtype), q0, jnp.zeros_like(q0), z,
                jnp.zeros((steps,), dtype), jnp.zeros((steps,), dtype))
        _, _, _, _, alpha, beta = jax.lax.fori_loop(0, steps, step, init)
        return alpha, beta

    return run


def lanczos_lambda2(g: DualGraph, *, dtype, steps: int, seed: int) -> float:
    """λ₂ by plain Lanczos (full reorthogonalisation, constants deflated)
    with every vector and the operator held in ``dtype`` on the default
    JAX device: the smallest Ritz value of the window.  The graph is
    padded to a power of two of rows, and the window is cut to half of
    that, so that it never outruns the graph and few shapes compile."""
    import jax.numpy as jnp

    n_pad = _pow2(max(g.n, 8))
    steps = min(steps, n_pad // 2)
    cols, vals, deg = ell_arrays(g, n_pad, _pow2(np.diff(g.adj.indptr).max()))
    mask = np.zeros(n_pad)
    mask[:g.n] = 1.0
    q0 = np.zeros(n_pad)
    q0[:g.n] = np.random.default_rng(seed % 2**64).standard_normal(g.n)
    q0[:g.n] -= q0[:g.n].mean()
    q0 /= np.linalg.norm(q0)
    alpha, beta = _lanczos_program(steps)(
        jnp.asarray(cols, jnp.int32), jnp.asarray(vals, dtype),
        jnp.asarray(deg, dtype), jnp.asarray(mask, dtype),
        jnp.asarray(q0, dtype))
    a = np.asarray(alpha.astype(jnp.float32), np.float64)
    b = np.asarray(beta.astype(jnp.float32), np.float64)
    T = np.diag(a) + np.diag(b[:-1], 1) + np.diag(b[:-1], -1)
    return float(np.linalg.eigvalsh(T)[0])
