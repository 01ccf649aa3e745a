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
     level's subgraphs via one vectorized multi-subgraph extraction.

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
from repro.core.fiedler import (
    _DENSE_CUTOFF,
    fiedler_from_graph,
    fiedler_from_graph_batched,
    fiedler_from_mesh,
    next_pow2,
)
from repro.core.rcb import rcb_order, rcb_order_segments, rib_order
from repro.guard.errors import SolverBreakdown
from repro.guard.policy import SolverGuard
from repro.mesh.graphs import Graph, dual_graph_from_incidence, extract_subgraphs

_ENGINES = ("batched", "recursive")


@dataclasses.dataclass
class BisectionRecord:
    level: int
    size: int
    nparts: int
    method: str
    iterations: int
    eigenvalue: float
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
                         ordered):
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
    keep the multilevel seeding but solve to tolerance.  The one remaining
    capped-without-warm-start case is a per-problem
    `multilevel_warm_start` numerical-breakdown fallback to noise inside a
    packed solve (the cap is per call, not per problem) — rare enough that
    the balanced-but-coarser bisection it risks is accepted."""
    if window is None:
        window = 20 if multilevel else 30
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
    """
    if engine not in _ENGINES:
        raise ValueError(f"unknown engine: {engine}")
    window, max_restarts = _resolve_solver_opts(
        window, max_restarts, multilevel, fine_restarts,
        ordered=(pre in ("rcb", "rib") and coords is not None
                 and not warm_start),
    )
    kw = dict(coords=coords, weights=weights, method=method, pre=pre, tol=tol,
              window=window, max_restarts=max_restarts, seed=seed,
              warm_start=warm_start, use_kernel=use_kernel,
              multilevel=multilevel, precond=precond, guard=guard)
    if engine == "batched":
        return _rsb_graph_batched(graph, nparts, **kw)
    return _rsb_graph_recursive(graph, nparts, **kw)


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
    seed, warm_start, use_kernel, multilevel, precond, guard=None,
) -> tuple[np.ndarray, RSBReport]:
    n = graph.n
    w = np.ones(n) if weights is None else np.asarray(weights, np.float64)
    records: list[BisectionRecord] = []
    levels: list[LevelRecord] = []
    parts = np.zeros(n, dtype=np.int64)
    sg = (SolverGuard(guard, seed=seed, method=method)
          if guard is not None and guard.enabled else None)
    with obs.timed("engine", engine="batched") as t_total:
        # Run-wide shape-bucket pins (see _rsb_mesh_batched): subgraph degrees
        # never exceed the root's, so the root ELL width bounds every level.
        pack_slots = next_pow2(max(n, 2))
        pack_segs = next_pow2(max(nparts, 1))
        root_width = int(graph.degrees.max()) if graph.nnz else 1
        width_pad = next_pow2(max(root_width, 2))

        active = [(graph, np.arange(n, dtype=np.int64), 0, nparts)]
        level = 0
        reorder = pre in ("rcb", "rib") and coords is not None
        while active:
            solve_nodes = []
            for node in active:
                _, idx, p_lo, p_hi = node
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
                        idxs = [idx for _, idx, _, _ in solve_nodes]
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
                            (g.sub(perm), idx[perm], p_lo, p_hi)
                            for (g, idx, p_lo, p_hi), perm
                            in zip(solve_nodes, perms)]
                with obs.timed("solve", level=level) as t_solve:
                    if sg is not None and sg.expired():
                        # Past the stage deadline: skip the level solve and
                        # let every node take the fallback rung below.
                        results = [None] * len(solve_nodes)
                    else:
                        results = fiedler_from_graph_batched(
                            [g for g, _, _, _ in solve_nodes],
                            method=method,
                            seeds=[_node_seed(seed, level, p_lo)
                                   for _, _, p_lo, _ in solve_nodes],
                            warms=[
                                _warm_vector(coords[idx])
                                if warm_start and coords is not None else None
                                for _, idx, _, _ in solve_nodes
                            ],
                            tol=tol, window=window, max_restarts=max_restarts,
                            pack_slots=pack_slots, pack_segs=pack_segs,
                            width_pad=width_pad, use_kernel=use_kernel,
                            multilevel=multilevel, precond=precond,
                        )
                if sg is not None:
                    # Re-admit every node's result; failed ones re-solve
                    # individually through the escalation ladder.
                    rescued = []
                    for (g, idx, p_lo, p_hi), res in zip(solve_nodes,
                                                         results):
                        def solve_fn(m, s, _g=g):
                            return fiedler_from_graph(
                                _g, method=m, order=None, seed=s, tol=tol,
                                window=window, max_restarts=max_restarts,
                                use_kernel=use_kernel, multilevel=multilevel,
                            )
                        rescued.append(_guarded(
                            sg, res, solve_fn, level=level, p_lo=p_lo,
                            size=int(idx.size),
                            coords_sub=coords[idx]
                            if coords is not None else None))
                    results = rescued
                with obs.timed("split", level=level) as t_split:
                    next_active = []
                    for (g, idx, p_lo, p_hi), res in zip(solve_nodes, results):
                        np_here = p_hi - p_lo
                        records.append(BisectionRecord(
                            level=level, size=int(idx.size), nparts=np_here,
                            method=res.method, iterations=res.iterations,
                            eigenvalue=res.eigenvalue, residual=res.residual,
                            seconds=t_solve.seconds / len(solve_nodes),
                            levels=res.levels, breakdown=res.breakdown,
                        ))
                        n_left = np_here // 2
                        lo, hi = _proportional_split(
                            res.vector, w[idx], n_left, np_here)
                        g_lo, g_hi = extract_subgraphs(g, [lo, hi])
                        next_active.append((g_lo, idx[lo], p_lo, p_lo + n_left))
                        next_active.append((g_hi, idx[hi], p_lo + n_left, p_hi))
            levels.append(LevelRecord(
                level=level,
                n_nodes=len(solve_nodes),
                total_size=sum(int(idx.size) for _, idx, _, _ in solve_nodes),
                buckets=_size_buckets(
                    [int(idx.size) for _, idx, _, _ in solve_nodes]
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
