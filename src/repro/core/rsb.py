"""Recursive Spectral Bisection driver (paper Algorithm 1).

Two engines share the same math:

**engine="batched"** (default) — the level-synchronous engine.  All 2^L
subdomains at level L of the bisection tree are independent (the paper
splits communicators so their Fiedler solves run concurrently; Sphynx maps
the same structure onto accelerator-batched linear algebra).  Each level:

  1. (optional) geometric pre-partitioning — RCB/RIB reorder of every
     active node's elements, all nodes in one segmented call (paper §8:
     ≈2× Lanczos speedup),
  2. every active subproblem is padded into a power-of-two
     (n_pad, width_pad) **shape bucket** and the whole bucket runs ONE
     jitted, vmapped Fiedler solve — batched ELL / gather-scatter Laplacian
     applies, batched Lanczos windows (or Jacobi-preconditioned inverse
     iteration with per-element-stopping batched flexcg), per-subproblem
     masks and per-subproblem convergence flags,
  3. a proportional split per node (sort by Fiedler component, cut at
     ⌊P/2⌋ / ⌈P/2⌉ of the weight — multi-material support) emits the next
     level's subgraphs via one vectorized multi-subgraph extraction, and
     one labelling pass finds the components of all of them.  A node
     whose subgraph is disconnected is not solved whole: its components
     join the level's batch, it reports λ₂ = 0 and is cut in (component,
     Fiedler value) order.  A node whose plain halves fall into pieces —
     a hub-dominated graph, where the Fiedler vector puts the periphery
     on one side and its hubs on the other — is split instead into
     unions of parts grown connected along its Fiedler vector
     (`core/split.py`); a mesh's halves are connected, so its splits are
     the plain ones.

Because the batched operators are *pytrees* handed to jit as traced
arguments, the run compiles one trace per shape bucket — a constant number
per run — instead of one trace per tree node.  That is what turns the
hardware-saturating batched matvecs into wall-clock wins, and the level
structure is exactly what `repro.dist` needs to later shard levels across
devices.

**Multilevel acceleration** (default on): every Fiedler solve runs
coarse-to-fine.  A Galerkin hierarchy per subproblem (host-built, the
`amg_setup` pairwise aggregation over the RCB ordering) is solved densely
at the coarsest level and prolonged cascadically to seed the device solve,
which is capped at `fine_restarts` refinement restarts over a shallower
Lanczos window; method="inverse" can additionally swap the Jacobi inner
preconditioner for the packed `BatchedAMG` V-cycle (`precond="amg"`).

**engine="recursive"** — the host-side depth-first recursion (one jitted
solve per tree node), kept for parity testing and as the AMG-preconditioned
inverse-iteration reference (AMG hierarchies are per-graph host state).

Load-balance invariant (paper Eq. 2.6): with unit weights, part sizes
differ by at most one element at every level — asserted in tests for both
engines.  Per-node Lanczos start vectors are seeded deterministically from
(seed, level, p_lo) so sibling subtrees never share a start vector.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.core import split
from repro.core.fiedler import (
    _DENSE_CUTOFF,
    ell_layout,
    fiedler_from_graph,
    fiedler_from_graph_batched,
    fiedler_from_mesh,
    next_pow2,
)
from repro.core.rcb import rcb_order, rcb_order_segments, rib_order
from repro.guard.errors import SolverBreakdown
from repro.guard.policy import SolverGuard
from repro.mesh.graphs import (
    Graph,
    connected_labels,
    dual_graph_from_incidence,
    extract_subgraphs,
    structural_order,
)

_ENGINES = ("batched", "recursive")


@dataclasses.dataclass
class BisectionRecord:
    level: int
    size: int
    nparts: int
    method: str
    iterations: int
    eigenvalue: float  # batched graph engine: the Rayleigh quotient of the
                       # vector the split used (0 for a disconnected node)
    residual: float
    seconds: float
    levels: int = 0    # multilevel hierarchy depth (warm start or AMG); 0 = none
    split_seconds: float = 0.0   # this node's sort/split + child extraction
    breakdown: bool = False      # solver breakdown (or guard fallback) here

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class LevelRecord:
    """One tree level of the engine: how many nodes were solved together,
    in which shape buckets, and where the time went."""

    level: int
    n_nodes: int             # nodes solved at this level
    total_size: int          # Σ elements over those nodes
    buckets: list            # [(count, n_pad)] — n_pad 0 = dense tail
    iterations: int          # Σ per-node restarts / outer iterations
    solve_seconds: float     # Fiedler solves (batched: the bucket solves)
    split_seconds: float     # sort/split + child extraction
    window: int = 0          # Lanczos window of the level's packed solve
                             # (0: no packed Lanczos solve at this level)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class RSBReport:
    records: list
    seconds: float
    levels: list = dataclasses.field(default_factory=list)
    engine: str = "recursive"
    pre: str = "none"          # geometric pre-partitioning used ("rcb"/"rib")
    precond: str = "none"      # inverse-iteration preconditioner ("jacobi"/"amg")
    multilevel: bool = False   # coarse-to-fine warm starts active
    post: object = None        # refine.PostStats once pipeline post stages ran
    ml: object = None          # multilevel.MultilevelStats (V-cycle bisect)
    guard: object = None       # guard.GuardReport: what degraded and why

    @property
    def total_iterations(self) -> int:
        return sum(r.iterations for r in self.records)

    @property
    def precond_levels(self) -> int:
        """Deepest multilevel hierarchy used by any solve (warm-start
        Galerkin ladder for Lanczos, AMG ladder for inverse iteration)."""
        return max((r.levels for r in self.records), default=0)

    def to_dict(self) -> dict:
        """JSON-able form — the one the benchmark rows and run manifests
        serialize instead of re-extracting fields by hand."""
        return {
            "engine": self.engine,
            "pre": self.pre,
            "precond": self.precond,
            "multilevel": self.multilevel,
            "seconds": self.seconds,
            "total_iterations": self.total_iterations,
            "precond_levels": self.precond_levels,
            "records": [r.to_dict() for r in self.records],
            "levels": [lv.to_dict() for lv in self.levels],
            "post": self.post.to_dict() if self.post is not None else None,
            "ml": self.ml.to_dict() if self.ml is not None else None,
            "guard": self.guard.to_dict() if self.guard is not None else None,
        }


def _node_seed(seed: int, level: int, p_lo: int, attempt: int = 0) -> int:
    """Deterministic per-node seed.  `seed + level` alone would hand every
    sibling at a level the identical Lanczos start vector; mixing in p_lo
    (the node's part range origin — unique per node within a level)
    decorrelates them.  `attempt` decorrelates guard retries: every retry
    (and its warm-start noise blend) draws a fresh start vector instead of
    replaying the identical failing solve; attempt=0 leaves the seed
    bit-identical to the pre-guard hash."""
    h = (seed * 0x9E3779B1 + level * 0x85EBCA77 + p_lo * 0xC2B2AE3D
         + attempt * 0x27D4EB2F) & 0x7FFFFFFF
    return int(h)


def _guarded(sg: SolverGuard | None, res, solve_fn, *, level: int,
             p_lo: int, size: int, coords_sub=None):
    """Admit one solve through the guard (no-op when unguarded).
    ``res`` may be None when the primary solve raised."""
    if sg is None:
        return res
    res2, why = sg.admit(res, level=level, p_lo=p_lo, size=size)
    if why is None:
        return res2
    return sg.rescue(solve_fn, why, level=level, p_lo=p_lo, size=size,
                     coords=coords_sub)


def _warm_vector(c: np.ndarray) -> np.ndarray:
    """Geometric warm start: centroid coordinate along the longest axis."""
    ax = int(np.argmax(c.max(0) - c.min(0)))
    return (c[:, ax] - c[:, ax].mean()).astype(np.float32)


def _proportional_split(keys: np.ndarray, weights: np.ndarray, n_left: int,
                        n_total: int) -> tuple[np.ndarray, np.ndarray]:
    order = np.argsort(keys, kind="stable")
    cw = np.cumsum(weights[order])
    target = cw[-1] * (n_left / n_total)
    k = int(np.searchsorted(cw, target, side="left")) + 1
    k = min(max(k, 1), keys.size - 1)
    return order[:k], order[k:]


def _rayleigh(g: Graph, v: np.ndarray) -> float:
    """The Rayleigh quotient on ``g``'s Laplacian of ``v`` with its mean
    removed, in float64: the λ₂ estimate of the vector a split is made
    from.  For a Ritz pair it is the Ritz value, to rounding."""
    v = np.asarray(v, np.float64)
    v = v - v.mean()
    av = np.bincount(g.rows, weights=g.weights * v[g.indices], minlength=g.n)
    deg = np.bincount(g.rows, weights=g.weights, minlength=g.n)
    vv = float(v @ v)
    return float(v @ (deg * v - av)) / vv if vv > 0 else 0.0


def _component_split(g: Graph, comp: np.ndarray, comp_groups: list,
                     comp_results: list, weights: np.ndarray, n_left: int,
                     n_total: int, ids: np.ndarray):
    """Split a disconnected node: order it by (component, the Fiedler
    value of the component's own subgraph), components by their lowest
    of ``ids``, as the plain reference's spectral order does, and cut at
    the weighted point."""
    values = np.zeros(g.n)
    for group, res in zip(comp_groups, comp_results):
        values[group] = split.sign_fixed(res.vector)
    keys = np.empty(g.n)
    keys[split.component_order(comp, values, ids)] = np.arange(g.n)
    return _proportional_split(keys, weights, n_left, n_total)


def _planned_split(g: Graph, weights: np.ndarray, f: np.ndarray, p: int,
                   plan: np.ndarray | None):
    """Halves of a connected node as unions of parts grown along its
    Fiedler vector ``f`` (:func:`split.part_plan`), with each half's plan:
    ``(lo, hi, plan_lo, plan_hi)``.  Where the fresh plan fails, the
    node's inherited ``plan`` is used; with none, returns None."""
    fresh, ok = split.part_plan(g, weights, f, p)
    if not ok:
        if plan is None:
            return None
        fresh = plan
    left, plan_lo, plan_hi = split.plan_halves(fresh, f, p)
    return np.flatnonzero(left), np.flatnonzero(~left), plan_lo, plan_hi


def _children_components(children: list) -> list:
    """Component labels and counts of every child subgraph of a level, in
    one labelling pass over the children's edges laid end to end."""
    offs = np.cumsum([0] + [c.n for c in children])
    src = np.concatenate([c.rows + o for c, o in zip(children, offs)])
    dst = np.concatenate([c.indices + o for c, o in zip(children, offs)])
    lab = connected_labels(int(offs[-1]), src, dst)
    out = []
    for a, b in zip(offs[:-1], offs[1:]):
        # Labels rank the components by their lowest node, so each child's
        # labels form one contiguous run.
        local = lab[a:b] - (lab[a] if b > a else 0)
        out.append((local, int(local.max()) + 1 if b > a else 0))
    return out


def _size_buckets(sizes: list) -> list:
    """Group node sizes into the (count, n_pad) shape buckets they solve in."""
    counts: dict = {}
    for s in sizes:
        key = 0 if s <= _DENSE_CUTOFF else next_pow2(s)
        counts[key] = counts.get(key, 0) + 1
    return sorted((c, k) for k, c in counts.items())


def _levels_from_records(records: list) -> list:
    """Aggregate per-node records into per-level records (recursive engine)."""
    by_level: dict = {}
    for r in records:
        by_level.setdefault(r.level, []).append(r)
    out = []
    for level in sorted(by_level):
        rs = by_level[level]
        out.append(LevelRecord(
            level=level,
            n_nodes=len(rs),
            total_size=sum(r.size for r in rs),
            buckets=_size_buckets([r.size for r in rs]),
            iterations=sum(r.iterations for r in rs),
            solve_seconds=sum(r.seconds for r in rs),
            split_seconds=sum(r.split_seconds for r in rs),
        ))
    return out


# ---------------------------------------------------------------------------
# Mesh drivers
# ---------------------------------------------------------------------------

def _resolve_solver_opts(window, max_restarts, multilevel, fine_restarts,
                         ordered, coordless=False):
    """Multilevel solves are *refinements* of the prolonged coarse Fiedler
    vector: a shallower Lanczos window (cheaper restarts AND a cheaper
    compiled trace) capped at a few restarts replaces the deep cold-start
    windows.  An explicit `window` always wins.

    The cap is only safe when the cascadic warm start is actually in play
    AND the geometric pre-ordering applied (`ordered`): pairwise
    aggregation follows the node order, so without RCB/RIB locality the
    hierarchy — and hence the warm start — is weaker, and a capped
    refinement would freeze a poorer bisection.  Unordered runs (and runs
    whose warm start comes from elsewhere — callers pass ordered=False)
    keep the multilevel seeding but solve to tolerance.  A graph without
    coordinates (`coordless`) solves over a 100-step window: on a
    skewed-degree graph, whose largest degree dwarfs the gap above λ₂ and
    where λ₃ may lie within 1% of λ₂, 20-step windows run into the restart
    cap short of tolerance (R-MAT scale 11: 50 restarts at each of the
    top three levels), where 100-step ones converge in 1 to 4 restarts a
    level for a fifth of the matvecs.  The one remaining
    capped-without-warm-start case is a per-problem
    `multilevel_warm_start` numerical-breakdown fallback to noise inside a
    packed solve (the cap is per call, not per problem) — rare enough that
    the balanced-but-coarser bisection it risks is accepted."""
    if window is None:
        window = (100 if coordless else 20) if multilevel else 30
    if multilevel and ordered and fine_restarts is not None:
        max_restarts = min(max_restarts, fine_restarts)
    return window, max_restarts


def rsb_partition_mesh(
    mesh,
    nparts: int,
    *,
    method: str = "lanczos",
    laplacian: str = "weighted",
    pre: str | None = "rcb",
    tol: float = 1e-3,
    window: int | None = None,
    max_restarts: int = 50,
    seed: int = 0,
    warm_start: bool = False,
    engine: str = "batched",
    multilevel: bool = True,
    fine_restarts: int | None = 3,
    precond: str = "jacobi",
    guard=None,
) -> tuple[np.ndarray, RSBReport]:
    """Partition a HexMesh into `nparts` via RSB on its dual graph.

    engine="batched" (default) solves every bisection of a tree level in
    one vmapped Fiedler solve per shape bucket; engine="recursive" is the
    sequential per-node reference.

    multilevel=True (default) runs every Fiedler solve coarse-to-fine: a
    Galerkin hierarchy per subproblem, a dense coarsest solve, a cascadic
    prolongation as the warm start, and the device solve capped at
    `fine_restarts` refinement restarts with a shallower default window
    (see `_resolve_solver_opts`).  `window=None` resolves to 20 under
    multilevel, 30 otherwise.

    `laplacian` is validated but currently a NO-OP: both settings
    partition the shared-vertex-weighted dual graph (the paper's ω
    weights); a genuinely unweighted operator is future work, so the
    benchmark rows labelled weighted/unweighted differ only in cache
    warmth.

    method="inverse" selects `precond`: "jacobi" (the batched default) or
    "amg" — the packed `BatchedAMG` V-cycle (paper §7) over
    leading-batch-dim operators.  The recursive engine always uses the
    per-graph host-built AMG hierarchy (the reference implementation).

    warm_start=True (beyond-paper) instead seeds the Fiedler solve with
    the centroid coordinate along the subset's longest axis; explicit warm
    starts take precedence over the multilevel ones.
    """
    if laplacian not in ("weighted", "unweighted"):
        raise ValueError(laplacian)
    if engine not in _ENGINES:
        raise ValueError(f"unknown engine: {engine}")
    window, max_restarts = _resolve_solver_opts(
        window, max_restarts, multilevel, fine_restarts,
        # warm_start=True replaces the cascadic warm start with the
        # geometric one — keep the pre-existing uncapped schedule there.
        ordered=pre in ("rcb", "rib") and not warm_start,
    )
    kw = dict(method=method, pre=pre, tol=tol, window=window,
              max_restarts=max_restarts, seed=seed, warm_start=warm_start,
              multilevel=multilevel, precond=precond, guard=guard)
    if engine == "batched":
        return _rsb_mesh_batched(mesh, nparts, **kw)
    return _rsb_mesh_recursive(mesh, nparts, **kw)


def _rsb_mesh_recursive(
    mesh, nparts, *, method, pre, tol, window, max_restarts, seed, warm_start,
    multilevel, precond, guard=None,
) -> tuple[np.ndarray, RSBReport]:
    records: list[BisectionRecord] = []
    parts = np.zeros(mesh.nelems, dtype=np.int64)
    sg = (SolverGuard(guard, seed=seed, method=method)
          if guard is not None and guard.enabled else None)

    def rec(idx: np.ndarray, p_lo: int, p_hi: int, level: int) -> None:
        np_here = p_hi - p_lo
        if np_here <= 1 or idx.size <= 1:
            parts[idx] = p_lo
            return
        # Geometric pre-partitioning: make active data locally contiguous.
        if pre in ("rcb", "rib"):
            fn = rcb_order if pre == "rcb" else rib_order
            idx = idx[fn(mesh.coords[idx], mesh.weights[idx])]

        sub_vg = mesh.vert_gid[idx]
        warm = _warm_vector(mesh.coords[idx]) if warm_start else None
        amg_cache: dict = {}

        def solve_fn(m, s, _sub_vg=sub_vg, _size=int(idx.size)):
            graph_amg = order_amg = None
            if m == "inverse":
                if "g" not in amg_cache:
                    uniq, inv = np.unique(_sub_vg, return_inverse=True)
                    amg_cache["g"] = dual_graph_from_incidence(
                        inv.reshape(_sub_vg.shape), uniq.size, _size
                    )
                graph_amg = amg_cache["g"]
                order_amg = np.arange(_size)  # already RCB-ordered above
            return fiedler_from_mesh(
                _sub_vg, method=m, graph_for_amg=graph_amg, order=order_amg,
                seed=s, tol=tol, window=window,
                max_restarts=max_restarts, warm=warm, multilevel=multilevel,
            )

        with obs.timed("solve", level=level, n=int(idx.size)) as t_solve:
            if sg is None:
                res = solve_fn(method, _node_seed(seed, level, p_lo))
            else:
                res = None
                if not sg.expired():  # past the stage deadline: skip straight
                    try:              # to the fallback rung inside rescue
                        res = solve_fn(method, _node_seed(seed, level, p_lo))
                    except SolverBreakdown:
                        res = None
                res = _guarded(sg, res, solve_fn, level=level, p_lo=p_lo,
                               size=int(idx.size),
                               coords_sub=mesh.coords[idx])
        n_left = np_here // 2
        with obs.timed("split", level=level) as t_split:
            lo, hi = _proportional_split(
                res.vector, mesh.weights[idx], n_left, np_here)
            idx_lo, idx_hi = idx[lo], idx[hi]
        records.append(BisectionRecord(
            level=level, size=int(idx.size), nparts=np_here, method=res.method,
            iterations=res.iterations, eigenvalue=res.eigenvalue,
            residual=res.residual, seconds=t_solve.seconds, levels=res.levels,
            split_seconds=t_split.seconds, breakdown=res.breakdown,
        ))
        rec(idx_lo, p_lo, p_lo + n_left, level + 1)
        rec(idx_hi, p_lo + n_left, p_hi, level + 1)

    with obs.timed("engine", engine="recursive") as t_total:
        rec(np.arange(mesh.nelems, dtype=np.int64), 0, nparts, 0)
    return parts, RSBReport(
        records=records, seconds=t_total.seconds,
        levels=_levels_from_records(records), engine="recursive",
        pre=pre or "none", precond="amg" if method == "inverse" else "none",
        multilevel=multilevel, guard=sg.report if sg is not None else None,
    )


def _rsb_mesh_batched(
    mesh, nparts, *, method, pre, tol, window, max_restarts, seed, warm_start,
    multilevel, precond, guard=None,
) -> tuple[np.ndarray, RSBReport]:
    """Level-synchronous mesh driver: delegate to the graph engine on the
    assembled dual graph.

    The multilevel pipeline (coarse-to-fine warm starts, batched AMG,
    dense tails) runs on assembled graphs, and the engine keeps every
    level's subgraphs current with one vectorized multi-subgraph
    extraction — so the assembled ELL operators come for free, their
    packed solve shares ONE compiled trace with every graph-path run of
    the same shape, and their matvecs are ~2× cheaper than the packed
    gather-scatter form on small subproblems.  The matrix-free
    gather-scatter solve (paper §5) remains the recursive mesh engine's
    and `fiedler_from_mesh_batched`'s path."""
    graph = dual_graph_from_incidence(mesh.vert_gid, mesh.n_vert, mesh.nelems)
    return _rsb_graph_batched(
        graph, nparts, coords=mesh.coords, weights=mesh.weights,
        method=method, pre=pre, tol=tol, window=window,
        max_restarts=max_restarts, seed=seed, warm_start=warm_start,
        use_kernel=False, multilevel=multilevel, precond=precond,
        guard=guard,
    )


# ---------------------------------------------------------------------------
# Graph drivers
# ---------------------------------------------------------------------------

def rsb_partition_graph(
    graph: Graph,
    nparts: int,
    *,
    coords: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    method: str = "lanczos",
    pre: str | None = "rcb",
    tol: float = 1e-3,
    window: int | None = None,
    max_restarts: int = 50,
    seed: int = 0,
    warm_start: bool = False,
    use_kernel: bool = False,
    engine: str = "batched",
    multilevel: bool = True,
    fine_restarts: int | None = 3,
    precond: str = "jacobi",
    guard=None,
) -> tuple[np.ndarray, RSBReport]:
    """Partition a generic graph (assembled ELL Laplacian) via RSB.

    `pre` selects the GEOMETRIC pre-partitioning pass ("rcb"/"rib"/None —
    paper §8), not a preconditioner; it defaults to "rcb" to match the
    mesh path and is a no-op when `coords` is not given.  The
    inverse-iteration preconditioner is `precond` ("jacobi" or "amg"),
    and `multilevel`/`fine_restarts`/`window` control the coarse-to-fine
    solver schedule exactly as in :func:`rsb_partition_mesh`.

    `use_kernel=True` routes every assembled ELL matvec through the Pallas
    `ell_spmv` kernel — both the packed 2-D Lanczos operator and the 3-D
    leading-batch-dim inverse-iteration operators (the batched grid
    kernel variant).

    This is the entry point the framework's partition-aware GNN sharding
    uses: feed the returned `parts` to
    `repro.dist.partition_aware.plan_halo_sharding` to get the shard_map
    halo plan whose all_gather volume is proportional to this cut.

    warm_start=True seeds each node's Fiedler solve from `coords` (the
    centroid coordinate along the subset's longest axis); it is a no-op
    without coords, and it takes precedence over the multilevel warm start.

    Without `coords`, "rcb"/"rib" take the structural order of
    :func:`repro.mesh.graphs.structural_order` in place of the geometric
    one: the graph is relabelled by it before the first solve, so the
    warm start's pairwise aggregation and the start vectors, which follow
    the node order, do the same work whatever order the caller's nodes
    come in.
    """
    if engine not in _ENGINES:
        raise ValueError(f"unknown engine: {engine}")
    window, max_restarts = _resolve_solver_opts(
        window, max_restarts, multilevel, fine_restarts,
        ordered=(pre in ("rcb", "rib") and coords is not None
                 and not warm_start),
        coordless=coords is None,
    )
    order = None
    if coords is None and pre in ("rcb", "rib"):
        order = structural_order(graph)
        graph = graph.sub(order)
        if weights is not None:
            weights = np.asarray(weights)[order]
    kw = dict(coords=coords, weights=weights, method=method, pre=pre, tol=tol,
              window=window, max_restarts=max_restarts, seed=seed,
              warm_start=warm_start, use_kernel=use_kernel,
              multilevel=multilevel, precond=precond, guard=guard)
    if engine == "batched":
        parts, report = _rsb_graph_batched(graph, nparts, ids=order, **kw)
    else:
        parts, report = _rsb_graph_recursive(graph, nparts, **kw)
    if order is not None:
        out = np.empty_like(parts)
        out[order] = parts
        parts = out
    return parts, report


def _rsb_graph_recursive(
    graph, nparts, *, coords, weights, method, pre, tol, window, max_restarts,
    seed, warm_start, use_kernel, multilevel, precond, guard=None,
) -> tuple[np.ndarray, RSBReport]:
    n = graph.n
    w = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    records: list[BisectionRecord] = []
    parts = np.zeros(n, dtype=np.int64)
    sg = (SolverGuard(guard, seed=seed, method=method)
          if guard is not None and guard.enabled else None)

    def rec(g: Graph, idx: np.ndarray, p_lo: int, p_hi: int, level: int) -> None:
        np_here = p_hi - p_lo
        if np_here <= 1 or idx.size <= 1:
            parts[idx] = p_lo
            return
        if pre in ("rcb", "rib") and coords is not None:
            fn = rcb_order if pre == "rcb" else rib_order
            perm = fn(coords[idx], w[idx])
            idx = idx[perm]
            g = g.sub(perm)
        warm = None
        if warm_start and coords is not None:
            warm = _warm_vector(coords[idx])

        def solve_fn(m, s, _g=g):
            return fiedler_from_graph(
                _g, method=m, order=None, seed=s,
                warm=warm, tol=tol, window=window, max_restarts=max_restarts,
                use_kernel=use_kernel, multilevel=multilevel,
            )

        with obs.timed("solve", level=level, n=int(idx.size)) as t_solve:
            if sg is None:
                res = solve_fn(method, _node_seed(seed, level, p_lo))
            else:
                res = None
                if not sg.expired():  # past the stage deadline: skip straight
                    try:              # to the fallback rung inside rescue
                        res = solve_fn(method, _node_seed(seed, level, p_lo))
                    except SolverBreakdown:
                        res = None
                res = _guarded(
                    sg, res, solve_fn, level=level, p_lo=p_lo,
                    size=int(idx.size),
                    coords_sub=coords[idx] if coords is not None else None)
        n_left = np_here // 2
        with obs.timed("split", level=level) as t_split:
            lo, hi = _proportional_split(res.vector, w[idx], n_left, np_here)
            g_lo, g_hi = g.sub(lo), g.sub(hi)
            idx_lo, idx_hi = idx[lo], idx[hi]
        records.append(BisectionRecord(
            level=level, size=int(idx.size), nparts=np_here, method=res.method,
            iterations=res.iterations, eigenvalue=res.eigenvalue,
            residual=res.residual, seconds=t_solve.seconds, levels=res.levels,
            split_seconds=t_split.seconds, breakdown=res.breakdown,
        ))
        rec(g_lo, idx_lo, p_lo, p_lo + n_left, level + 1)
        rec(g_hi, idx_hi, p_lo + n_left, p_hi, level + 1)

    with obs.timed("engine", engine="recursive") as t_total:
        rec(graph, np.arange(n, dtype=np.int64), 0, nparts, 0)
    return parts, RSBReport(
        records=records, seconds=t_total.seconds,
        levels=_levels_from_records(records), engine="recursive",
        pre=pre or "none", precond="amg" if method == "inverse" else "none",
        multilevel=multilevel, guard=sg.report if sg is not None else None,
    )


def _rsb_graph_batched(
    graph, nparts, *, coords, weights, method, pre, tol, window, max_restarts,
    seed, warm_start, use_kernel, multilevel, precond, guard=None, ids=None,
) -> tuple[np.ndarray, RSBReport]:
    """The level-synchronous engine (module docstring).  ``ids`` numbers
    the nodes as the caller's graph does, where the engine runs on a
    relabelled one: a disconnected node orders its components by them."""
    n = graph.n
    ids = np.arange(n, dtype=np.int64) if ids is None else ids
    w = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    records: list[BisectionRecord] = []
    levels: list[LevelRecord] = []
    parts = np.zeros(n, dtype=np.int64)
    sg = (SolverGuard(guard, seed=seed, method=method)
          if guard is not None and guard.enabled else None)
    with obs.timed("engine", engine="batched") as t_total:
        # Run-wide shape-bucket pins (see _rsb_mesh_batched): subgraph degrees
        # never exceed the root's and a level's nodes are disjoint, so the
        # root's ELL layout (head width, overflow rows) holds every level.
        pack_slots = next_pow2(max(n, 2))
        pack_segs = next_pow2(max(nparts, 1))
        width_pad, overflow_rows = ell_layout(graph.degrees, pack_slots)

        # Each active node: (graph, element ids, p_lo, p_hi, plan, comp).
        # `plan` is the part plan a connected split left this node (None
        # where its parent split plainly); `comp` its (component labels,
        # count), found once per level for all children together.
        comp0 = split.components(graph)
        active = [(graph, np.arange(n, dtype=np.int64), 0, nparts, None, comp0)]
        level = 0
        reorder = pre in ("rcb", "rib") and coords is not None
        while active:
            solve_nodes = []
            for node in active:
                _, idx, p_lo, p_hi, _, _ = node
                if p_hi - p_lo <= 1 or idx.size <= 1:
                    parts[idx] = p_lo
                else:
                    solve_nodes.append(node)
            if not solve_nodes:
                break

            with obs.span(f"level:{level}", nodes=len(solve_nodes)):
                if reorder:
                    # Two passes over the level's nodes, so the geometric
                    # orders and the subgraph relabels are timed apart.  All
                    # nodes are ordered in one segmented call.
                    with obs.timed("reorder", level=level):
                        idxs = [node[1] for node in solve_nodes]
                        cat = np.concatenate(idxs)
                        bounds = np.cumsum([0] + [idx.size for idx in idxs])
                        order, passes = rcb_order_segments(
                            coords[cat], w[cat], bounds,
                            inertial=pre == "rib")
                        obs.counter_add("reorder_passes", passes)
                        perms = [order[a:b] - a
                                 for a, b in zip(bounds[:-1], bounds[1:])]
                    with obs.timed("sub", level=level):
                        solve_nodes = [
                            (g.sub(perm), idx[perm], p_lo, p_hi,
                             None if plan is None else plan[perm],
                             (comp[0][perm], comp[1]))
                            for (g, idx, p_lo, p_hi, plan, comp), perm
                            in zip(solve_nodes, perms)]
                # A disconnected node is not solved whole: each component
                # of two or more nodes joins the level's batch instead.
                with obs.timed("split_components", level=level):
                    problems, owner, comp_parts = [], [], []
                    for i, (g, idx, p_lo, p_hi, _, (lab, ncomp)) in enumerate(
                            solve_nodes):
                        if ncomp <= 1:
                            owner.append((i, -1))
                            problems.append(g)
                            comp_parts.append(None)
                            continue
                        sizes = np.bincount(lab, minlength=ncomp)
                        groups = [np.flatnonzero(lab == c)
                                  for c in np.flatnonzero(sizes > 1)]
                        comp_parts.append(groups)
                        subs = extract_subgraphs(g, groups) if groups else []
                        for k, sub in enumerate(subs):
                            owner.append((i, k))
                            problems.append(sub)
                    obs.counter_add(
                        "comp_split_nodes",
                        sum(groups is not None for groups in comp_parts))
                node_of = [solve_nodes[i] for i, _ in owner]
                seeds = [_node_seed(seed, level, node[2], attempt=k + 1)
                         if k >= 0 else _node_seed(seed, level, node[2])
                         for node, (_, k) in zip(node_of, owner)]
                with obs.timed("solve", level=level) as t_solve:
                    if sg is not None and sg.expired():
                        # Past the stage deadline: skip the level solve and
                        # let every node take the fallback rung below.
                        results = [None] * len(problems)
                    else:
                        results = fiedler_from_graph_batched(
                            problems,
                            method=method,
                            seeds=seeds,
                            warms=[
                                _warm_vector(coords[node[1]])
                                if warm_start and coords is not None
                                and k < 0 else None
                                for node, (_, k) in zip(node_of, owner)
                            ],
                            tol=tol, window=window, max_restarts=max_restarts,
                            pack_slots=pack_slots, pack_segs=pack_segs,
                            width_pad=width_pad,
                            overflow_rows=overflow_rows,
                            use_kernel=use_kernel,
                            multilevel=multilevel, precond=precond,
                        ) if problems else []
                if sg is not None:
                    # Re-admit every result; failed ones re-solve
                    # individually through the escalation ladder.
                    rescued = []
                    for g, node, (_, k), res in zip(problems, node_of, owner,
                                                    results):
                        def solve_fn(m, s, _g=g):
                            return fiedler_from_graph(
                                _g, method=m, order=None, seed=s, tol=tol,
                                window=window, max_restarts=max_restarts,
                                use_kernel=use_kernel, multilevel=multilevel,
                            )
                        rescued.append(_guarded(
                            sg, res, solve_fn, level=level, p_lo=node[2],
                            size=g.n,
                            coords_sub=coords[node[1]]
                            if coords is not None and k < 0 else None))
                    results = rescued
                node_results: list = [[] for _ in solve_nodes]
                for (i, _), res in zip(owner, results):
                    node_results[i].append(res)
                with obs.timed("split", level=level) as t_split:
                    halves = []
                    for node, groups, res_list in zip(solve_nodes, comp_parts,
                                                      node_results):
                        g, idx, p_lo, p_hi, plan, (lab, _) = node
                        np_here, n_left = p_hi - p_lo, (p_hi - p_lo) // 2
                        if groups is not None:
                            records.append(BisectionRecord(
                                level=level, size=int(idx.size),
                                nparts=np_here, method="components",
                                iterations=sum(r.iterations for r in res_list),
                                eigenvalue=0.0, residual=0.0,
                                seconds=t_solve.seconds / len(solve_nodes),
                                levels=max((r.levels for r in res_list),
                                           default=0),
                                breakdown=any(r.breakdown for r in res_list),
                            ))
                            lo, hi = _component_split(
                                g, lab, groups, res_list, w[idx], n_left,
                                np_here, ids[idx])
                            halves.append((lo, hi, None, None))
                            continue
                        res = res_list[0]
                        records.append(BisectionRecord(
                            level=level, size=int(idx.size), nparts=np_here,
                            method=res.method, iterations=res.iterations,
                            eigenvalue=_rayleigh(g, res.vector),
                            residual=res.residual,
                            seconds=t_solve.seconds / len(solve_nodes),
                            levels=res.levels, breakdown=res.breakdown,
                        ))
                        if plan is None:
                            lo, hi = _proportional_split(
                                res.vector, w[idx], n_left, np_here)
                            halves.append((lo, hi, None, None))
                        else:
                            halves.append(_planned_split(
                                g, w[idx], res.vector, np_here, plan))
                    children = [c for node, (lo, hi, _, _)
                                in zip(solve_nodes, halves)
                                for c in extract_subgraphs(node[0], [lo, hi])]
                    comps = _children_components(children)
                    # Where a plain split left a half in pieces, grow the
                    # node's parts as connected regions instead.
                    grown = 0
                    for i, node in enumerate(solve_nodes):
                        g, idx, p_lo, p_hi, plan, (_, ncomp) = node
                        if (plan is not None or ncomp > 1
                                or comp_parts[i] is not None
                                or (comps[2 * i][1] <= 1
                                    and comps[2 * i + 1][1] <= 1)):
                            continue
                        fresh = _planned_split(g, w[idx],
                                               node_results[i][0].vector,
                                               p_hi - p_lo, None)
                        if fresh is None:
                            continue
                        grown += 1
                        halves[i] = fresh
                        children[2 * i:2 * i + 2] = extract_subgraphs(
                            g, [fresh[0], fresh[1]])
                        comps[2 * i:2 * i + 2] = _children_components(
                            children[2 * i:2 * i + 2])
                    obs.counter_add("grown_splits", grown)
                    next_active = []
                    for i, (node, (lo, hi, plan_lo, plan_hi)) in enumerate(
                            zip(solve_nodes, halves)):
                        g, idx, p_lo, p_hi, _, _ = node
                        mid = p_lo + (p_hi - p_lo) // 2
                        next_active.append((children[2 * i], idx[lo], p_lo,
                                            mid, plan_lo, comps[2 * i]))
                        next_active.append((children[2 * i + 1], idx[hi], mid,
                                            p_hi, plan_hi, comps[2 * i + 1]))
            levels.append(LevelRecord(
                level=level,
                n_nodes=len(solve_nodes),
                total_size=sum(int(node[1].size) for node in solve_nodes),
                buckets=_size_buckets(
                    [int(node[1].size) for node in solve_nodes]
                ),
                iterations=sum(r.iterations for r in results),
                solve_seconds=t_solve.seconds,
                split_seconds=t_split.seconds,
                window=window if method == "lanczos" and any(
                    r.method == "lanczos" for r in results) else 0,
            ))
            # Per-node split cost isn't separable in the level-synchronous
            # engine; attribute the level's split evenly so engine comparisons
            # on summed split_seconds stay apples-to-apples.
            for r in records[-len(solve_nodes):]:
                r.split_seconds = t_split.seconds / len(solve_nodes)
            active = next_active
            level += 1

    return parts, RSBReport(
        records=records, seconds=t_total.seconds,
        levels=levels, engine="batched", pre=pre or "none",
        precond=precond if method == "inverse" else "none",
        multilevel=multilevel, guard=sg.report if sg is not None else None,
    )


def partition(obj, nparts: int, **kw) -> np.ndarray:
    """Uniform front door: partitioner ∈ {rsb, rsb_inverse, rcb, rib, sfc,
    random}.  Compatibility wrapper over the composable stage pipeline —
    see :func:`repro.core.pipeline.partition` for the full surface
    (``refine=`` post stages, explicit per-stage kwarg routing) and
    :class:`repro.core.pipeline.PartitionPipeline` for report + timings.
    """
    from repro.core.pipeline import partition as _pipeline_partition

    return _pipeline_partition(obj, nparts, **kw)
