"""Composable partition pipeline: pre → bisect → post.

parRSB's quality claims rest on a *pipeline*, not on raw bisection labels:
geometric pre-partitioning, spectral bisection on the dual graph, then
post-processing that repairs disconnected parts and smooths boundaries.
This module turns that shape into the front door of the partitioning
stack:

* :class:`PartitionPipeline` — three stage slots.
  - ``pre``    ∈ {"rcb", "rib", "sfc", "none"}.  For spectral bisect
    stages, "rcb"/"rib" select the *per-level* geometric reordering the
    RSB drivers apply at every tree node (paper §8 — threaded through as
    the drivers' ``pre=``, because the reorder must follow the recursion);
    "sfc" applies ONE global space-filling-curve permutation up front (the
    ordering bootstrap for the order-following multilevel hierarchy).
    Geometric bisect stages are their own geometry and ignore ``pre``.
  - ``bisect`` ∈ {"rsb-batched", "rsb-recursive", "multilevel", "rcb",
    "rib", "sfc", "random"} — a registered stage producing the labels (the
    geometric partitioners are ordinary stages here, not special cases;
    "multilevel" is the METIS-style coarsen→partition→prolong+refine
    V-cycle in :mod:`repro.core.multilevel` — no eigensolves on the fine
    graph, the raw-speed engine at scale).
  - ``post``   — an ordered tuple of registered refiners, by default
    ``("repair", "refine")``: connected-component repair then greedy
    weighted FM boundary sweeps (:mod:`repro.core.refine`), both
    cut-non-increasing.  ``("repair", "kway")`` swaps the greedy sweeps
    for the hill-climbing k-way FM (:mod:`repro.core.kway` — negative-gain
    prefixes, rollback to the best prefix).  The "refine"/"kway" stages
    close with a repair pass so the zero-disconnected-parts invariant
    survives articulation moves.  One balance corridor — computed from the
    part weights the chain starts with — governs the whole chain
    (:func:`run_post_stages`).

* :class:`PartitionContext` — what flows through the stages: the
  mesh/graph, coords, weights, the evolving ``parts``, the
  :class:`~repro.core.rsb.RSBReport` (whose ``post`` section the post
  stages fill in), and one :class:`StageRecord` per stage with wall-clock
  and stage-specific info.  Consumers that want more than labels
  (``plan_halo_sharding``, the benchmark tables, the smoke gate) take the
  context itself.

* :func:`partition` — the compatibility front door `rsb.partition`
  forwards to.  It builds a pipeline from the classic keyword surface
  (``partitioner=``, ``engine=``, plus the new ``refine=`` escape hatch,
  default on for RSB) and returns only the label array.  Stage kwargs are
  routed explicitly and unknown keys raise — ``sfc_parts`` no longer
  silently drops ``curve``/``bits``.

Adding a quality optimization is now "register a stage", not "grow the
driver": see ``register_post_stage`` and the README's stage contract.
"""

from __future__ import annotations

import contextlib
import dataclasses
import inspect
import os

import numpy as np

from repro import obs
from repro.core.kway import kway_stage
from repro.core.refine import (
    PostStats,
    balance_corridor,
    refine_stage,
    repair_components,
)
from repro.core.rsb import RSBReport, rsb_partition_graph, rsb_partition_mesh
from repro.guard import chaos
from repro.guard.errors import GuardReport
from repro.guard.policy import GuardPolicy, check_output, enforce_output
from repro.guard.validate import (
    component_labels,
    pack_components,
    proportional_budgets,
    validate_graph,
    validate_mesh,
    validate_nparts,
)
from repro.mesh.graphs import Graph, dual_graph_from_incidence


@dataclasses.dataclass
class StageRecord:
    """One executed stage: where the wall-clock went and what it did."""

    kind: str          # "pre" | "bisect" | "post"
    name: str
    seconds: float
    info: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"kind": self.kind, "name": self.name,
                "seconds": self.seconds, **self.info}

    @classmethod
    def from_span(cls, span, kind: str, name: str, info: dict | None = None):
        """Derive the record from a completed obs span (single source of
        wall-clock truth when tracing is active)."""
        return cls(kind=kind, name=name, seconds=span.seconds,
                   info=dict(info or {}))


@dataclasses.dataclass
class PartitionContext:
    """State threaded through the pipeline stages."""

    nparts: int
    mesh: object | None = None          # HexMesh input (None for graphs)
    graph: Graph | None = None          # dual graph (built lazily for meshes)
    coords: np.ndarray | None = None
    weights: np.ndarray | None = None
    parts: np.ndarray | None = None     # current labels (post stages mutate)
    parts_raw: np.ndarray | None = None  # bisect output, before any post stage
    report: RSBReport | None = None
    stages: list = dataclasses.field(default_factory=list)  # [StageRecord]
    trace: object | None = None          # obs.Span root (None: REPRO_OBS=off)
    config: dict = dataclasses.field(default_factory=dict)  # pipeline shape

    @property
    def n(self) -> int:
        return self.mesh.nelems if self.mesh is not None else self.graph.n

    def require_graph(self) -> Graph:
        """The dual graph — assembled on first use for mesh inputs."""
        if self.graph is None:
            m = self.mesh
            self.graph = dual_graph_from_incidence(m.vert_gid, m.n_vert,
                                                   m.nelems)
        return self.graph

    def stage_seconds(self, kind: str | None = None) -> float:
        return sum(s.seconds for s in self.stages
                   if kind is None or s.kind == kind)

    @property
    def seconds(self) -> float:
        return self.stage_seconds()

    def stats(self) -> dict:
        """JSON-able run summary (benchmark rows, experiment records)."""
        out = {
            "nparts": self.nparts,
            "n": self.n,
            "seconds": self.seconds,
            "stages": [s.to_dict() for s in self.stages],
        }
        if self.report is not None and self.report.post is not None:
            out["post"] = self.report.post.row()
        return out

    def export_manifest(self, path: str | None = None, *,
                        name: str = "partition",
                        runs_dir: str = "runs") -> str | None:
        """Write this run's JSONL manifest (span tree + counters + config
        + git SHA).  Returns the path, or None when no trace was recorded
        (``REPRO_OBS=off``)."""
        if self.trace is None:
            return None
        if path is None:
            path = obs.run_path(runs_dir, name)
        return obs.write_manifest(self.trace, path, name=name,
                                  config=self.config)

    def export_trace_events(self, path: str) -> str | None:
        """Write the Chrome/Perfetto ``trace_event`` JSON for this run;
        None when no trace was recorded."""
        if self.trace is None:
            return None
        return obs.write_trace_events(self.trace, path)


# ---------------------------------------------------------------------------
# Stage registries
# ---------------------------------------------------------------------------

PRE_STAGES = ("rcb", "rib", "sfc", "none")

_BISECT_STAGES: dict = {}
_POST_STAGES: dict = {}


def register_bisect_stage(name: str, fn) -> None:
    """Register ``fn(ctx, pre, **kw) -> (parts, RSBReport | None)``.

    ``pre`` is the pipeline's pre-stage hint ("rcb"/"rib"/None) for stages
    that thread a per-level reordering; geometric stages may ignore it.
    """
    _BISECT_STAGES[name] = fn


def register_post_stage(name: str, fn) -> None:
    """Register ``fn(graph, parts, nparts, *, weights=None, ...) ->
    (parts, PostStats)``.  The stage must be cut-non-increasing and must
    not change the label domain ``0..nparts-1``.  The pipeline's
    ``post_kw`` is filtered against the stage's signature (declare the
    keywords you consume — e.g. "repair" takes ``balance_tol`` but not
    ``sweeps``; a ``**kw`` catch-all receives everything)."""
    _POST_STAGES[name] = fn


def bisect_stage_names() -> tuple:
    return tuple(sorted(_BISECT_STAGES))


def post_stage_names() -> tuple:
    return tuple(sorted(_POST_STAGES))


def _rsb_stage(engine):
    def stage(ctx: PartitionContext, pre, **kw):
        if ctx.mesh is not None:
            if engine == "batched":
                # The batched mesh driver only assembles the dual graph and
                # delegates to the graph driver; assembling through the
                # context instead builds the graph ONCE per run — the post
                # stages (and any metrics consumer) reuse it.
                laplacian = kw.pop("laplacian", "weighted")
                if laplacian not in ("weighted", "unweighted"):
                    raise ValueError(laplacian)
                return rsb_partition_graph(
                    ctx.require_graph(), ctx.nparts, coords=ctx.coords,
                    weights=ctx.weights, pre=pre, engine=engine, **kw)
            # The recursive mesh driver reads coords/weights off the mesh;
            # honor caller overrides by handing it an overridden copy so
            # both engines balance the same weights.
            mesh = ctx.mesh
            if ctx.coords is not mesh.coords or ctx.weights is not mesh.weights:
                mesh = dataclasses.replace(
                    mesh, coords=np.asarray(ctx.coords, np.float64),
                    weights=np.asarray(ctx.weights, np.float64))
            return rsb_partition_mesh(mesh, ctx.nparts, pre=pre,
                                      engine=engine, **kw)
        return rsb_partition_graph(ctx.require_graph(), ctx.nparts,
                                   coords=ctx.coords, weights=ctx.weights,
                                   pre=pre, engine=engine, **kw)
    return stage


def _geometric_stage(fn):
    def stage(ctx: PartitionContext, pre, **kw):
        if ctx.coords is None:
            raise ValueError("geometric bisect stages need coords")
        return fn(ctx.coords, ctx.nparts, ctx.weights, **kw), None
    return stage


def _random_stage(ctx: PartitionContext, pre, *, seed: int = 0):
    rng = np.random.default_rng(seed)
    return rng.permutation(np.arange(ctx.n) % ctx.nparts), None


def _multilevel_stage(ctx: PartitionContext, pre, **kw):
    """METIS-style multilevel k-way V-cycle (repro.core.multilevel):
    coarsen → partition-coarsest → prolong+refine.  Purely combinatorial —
    the ``pre`` reorder hint is irrelevant (matching is order-free)."""
    from repro.core.multilevel import multilevel_partition

    return multilevel_partition(ctx.require_graph(), ctx.nparts,
                                weights=ctx.weights, **kw)


def _stage_kw(fn, post_kw: dict) -> dict:
    """Filter ``post_kw`` to the keywords ``fn``'s signature accepts
    (everything passes through a ``**kw`` catch-all)."""
    params = inspect.signature(fn).parameters
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return dict(post_kw)
    return {k: v for k, v in post_kw.items() if k in params}


def _refine_sharded_stage(graph, parts, nparts, *, weights=None, sweeps=4,
                          balance_tol=0.05, corridor=None, backend="auto",
                          guard=None):
    """Device-resident sharded boundary refinement (repro.dist).  The
    signature mirrors dist.refine_sharded.refine_sharded_stage so
    ``_stage_kw`` filters correctly; the import is lazy because the dist
    layer imports this module's PartitionContext."""
    from repro.dist.refine_sharded import refine_sharded_stage
    return refine_sharded_stage(graph, parts, nparts, weights=weights,
                                sweeps=sweeps, balance_tol=balance_tol,
                                corridor=corridor, backend=backend,
                                guard=guard)


def _kway_sharded_stage(graph, parts, nparts, *, weights=None, sweeps=4,
                        passes=2, balance_tol=0.05, corridor=None,
                        backend="auto", guard=None):
    """Sharded sweeps + host boundary k-way polish (repro.dist)."""
    from repro.dist.refine_sharded import kway_sharded_stage
    return kway_sharded_stage(graph, parts, nparts, weights=weights,
                              sweeps=sweeps, passes=passes,
                              balance_tol=balance_tol, corridor=corridor,
                              backend=backend, guard=guard)


def _register_builtin_stages() -> None:
    from repro.core.rcb import rcb_parts, rib_parts
    from repro.core.sfc import sfc_parts

    register_bisect_stage("rsb-batched", _rsb_stage("batched"))
    register_bisect_stage("rsb-recursive", _rsb_stage("recursive"))
    register_bisect_stage("rcb", _geometric_stage(
        lambda c, p, w, **kw: rcb_parts(c, p, w, **kw)))
    register_bisect_stage("rib", _geometric_stage(
        lambda c, p, w, **kw: rib_parts(c, p, w, **kw)))
    register_bisect_stage("sfc", _geometric_stage(
        lambda c, p, w, **kw: sfc_parts(c, p, w, **kw)))
    register_bisect_stage("random", _random_stage)
    register_bisect_stage("multilevel", _multilevel_stage)
    # The refine.py/kway.py functions ARE the stages (their signatures
    # declare the keywords each consumes; refine_stage and kway_stage close
    # with a repair pass so the zero-disconnected invariant survives FM
    # articulation moves).
    register_post_stage("repair", repair_components)
    register_post_stage("refine", refine_stage)
    register_post_stage("kway", kway_stage)
    register_post_stage("refine-sharded", _refine_sharded_stage)
    register_post_stage("kway-sharded", _kway_sharded_stage)


_register_builtin_stages()


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------

def _make_context(obj, nparts, coords, weights) -> PartitionContext:
    is_mesh = hasattr(obj, "vert_gid")
    if is_mesh:
        c = obj.coords if coords is None else coords
        w = obj.weights if weights is None else weights
        return PartitionContext(nparts=nparts, mesh=obj, coords=c, weights=w)
    return PartitionContext(nparts=nparts, graph=obj, coords=coords,
                            weights=weights)


def _permuted_input(ctx: PartitionContext, order: np.ndarray):
    """A new context whose input is reordered by ``order`` (pre="sfc"),
    carrying any caller coords/weights overrides along."""
    if ctx.mesh is not None:
        mesh = ctx.mesh.take(order)
        if (ctx.coords is not ctx.mesh.coords
                or ctx.weights is not ctx.mesh.weights):
            mesh = dataclasses.replace(
                mesh, coords=np.asarray(ctx.coords, np.float64)[order],
                weights=np.asarray(ctx.weights, np.float64)[order])
        return PartitionContext(nparts=ctx.nparts, mesh=mesh,
                                coords=mesh.coords, weights=mesh.weights)
    return PartitionContext(
        nparts=ctx.nparts, graph=ctx.graph.sub(order),
        coords=None if ctx.coords is None else ctx.coords[order],
        weights=None if ctx.weights is None else ctx.weights[order],
    )


def _subset_context(ctx: PartitionContext, idx: np.ndarray,
                    nparts: int) -> PartitionContext:
    """A sub-context over the nodes in ``idx`` (one connected component),
    renumbered contiguously — what the per-component bisect runs on."""
    if ctx.mesh is not None:
        mesh = ctx.mesh.take(idx)
        return PartitionContext(nparts=nparts, mesh=mesh,
                                coords=mesh.coords, weights=mesh.weights)
    return PartitionContext(
        nparts=nparts, graph=ctx.require_graph().sub(idx),
        coords=None if ctx.coords is None else ctx.coords[idx],
        weights=None if ctx.weights is None else ctx.weights[idx],
    )


def _guard_enabled(flag: bool | None) -> bool:
    """Resolve the pipeline guard switch: an explicit ``guard=`` wins;
    otherwise ``REPRO_GUARD`` (default on; off/0/false/no disable)."""
    if flag is not None:
        return bool(flag)
    env = os.environ.get("REPRO_GUARD", "on").strip().lower()
    return env not in ("off", "0", "false", "no")


def _merge_guard(dst: GuardReport, src) -> None:
    """Fold one bisect stage's GuardReport into the pipeline-wide one
    (the RSB drivers create their own per-stage report)."""
    if src is None or src is dst:
        return
    dst.validated |= src.validated
    dst.sanitized |= src.sanitized
    dst.issues.extend(src.issues)
    dst.components = max(dst.components, src.components)
    dst.retries += src.retries
    dst.fallbacks += src.fallbacks
    dst.sanitize_fixes += src.sanitize_fixes
    dst.deadline_expired |= src.deadline_expired
    dst.degraded.extend(src.degraded)


def run_post_stages(
    graph: Graph,
    parts: np.ndarray,
    nparts: int,
    post: tuple,
    *,
    weights: np.ndarray | None = None,
    post_kw: dict | None = None,
) -> tuple[np.ndarray, PostStats, list]:
    """Run an ordered chain of registered post stages over ``parts``.

    The balance corridor is computed ONCE here — from the part weights the
    chain starts with — and threaded through every stage, so one stage's
    moves cannot shift the corridor for the stages after it (callers may pre-seed ``post_kw["corridor"]`` to
    pin an even earlier reference).  Returns the refined labels, the
    aggregated :class:`PostStats`, and one :class:`StageRecord` per stage.

    This is what :meth:`PartitionPipeline.run` executes after the bisect
    stage; benchmarks call it directly on a context's ``parts_raw`` to
    compare post chains (e.g. greedy vs k-way) from ONE bisection solve.
    """
    post_kw = dict(post_kw or {})
    parts = np.asarray(parts, dtype=np.int64)
    if post_kw.get("corridor") is None:
        post_kw["corridor"] = balance_corridor(
            parts, nparts, weights, post_kw.get("balance_tol", 0.05))
    corridor = post_kw["corridor"]
    agg = PostStats(corridor=tuple(corridor))
    records = []
    for i, name in enumerate(post):
        fn = _POST_STAGES[name]
        with obs.timed(f"post:{name}") as t:
            parts, stats = fn(graph, parts, nparts, weights=weights,
                              **_stage_kw(fn, post_kw))
        dt = t.seconds
        parts = np.asarray(parts, dtype=np.int64)
        agg.stages.append(name)
        agg.fragments_repaired += stats.fragments_repaired
        # final state, not a sum: a later repair can clear earlier
        # stages' leftovers
        agg.unrepaired_fragments = stats.unrepaired_fragments
        agg.moves_applied += stats.moves_applied
        agg.sweeps.extend(stats.sweeps)
        if stats.kway is not None:
            agg.kway = stats.kway
        agg.seconds += dt
        records.append(StageRecord(
            kind="post", name=name, seconds=dt,
            info={"stages": list(stats.stages),
                  "cut_before": stats.cut_before,
                  "cut_after": stats.cut_after,
                  "fragments": stats.fragments_repaired,
                  "moves": stats.moves_applied,
                  "corridor": tuple(stats.corridor)
                  if stats.corridor else None},
        ))
        if i == 0:
            agg.cut_before = stats.cut_before
        agg.cut_after = stats.cut_after
    return parts, agg, records


@dataclasses.dataclass
class PartitionPipeline:
    """pre → bisect → post, each slot a registered stage (module docstring).

    ``bisect_kw`` goes to the bisect stage verbatim; ``post_kw`` to every
    post stage, filtered against each stage's signature (the built-ins
    share the ``balance_tol`` surface; ``sweeps`` is declared — and hence
    received — by "refine" only).

    ``guard`` switches the fault-tolerance envelope (:mod:`repro.guard`):
    validation front door before ``pre``, per-component dispatch for
    disconnected inputs, a :class:`~repro.guard.policy.SolverGuard` around
    every spectral solve, and the output-invariant finalizer after
    ``post``.  ``None`` defers to ``REPRO_GUARD`` (default on).
    ``guard_kw`` parameterizes the :class:`~repro.guard.policy.GuardPolicy`
    (``sanitize``, ``max_retries``, ``switch_method``, ``deadline``,
    ``balance_tol``) plus the chaos overlay (``chaos`` — fault-site tuple —
    ``chaos_seed``, ``chaos_rate``).  A healthy guarded run returns labels
    bit-identical to ``guard=False``: the guard only *mutates* on failure.
    """

    pre: str = "rcb"
    bisect: str = "rsb-batched"
    post: tuple = ("repair", "refine")
    bisect_kw: dict = dataclasses.field(default_factory=dict)
    post_kw: dict = dataclasses.field(default_factory=dict)
    guard: bool | None = None
    guard_kw: dict = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.pre not in PRE_STAGES:
            raise ValueError(
                f"unknown pre stage: {self.pre!r} (have {PRE_STAGES})")
        if self.bisect not in _BISECT_STAGES:
            raise ValueError(
                f"unknown bisect stage: {self.bisect!r} "
                f"(have {bisect_stage_names()})")
        self.post = tuple(self.post)
        for name in self.post:
            if name not in _POST_STAGES:
                raise ValueError(
                    f"unknown post stage: {name!r} "
                    f"(have {post_stage_names()})")

    def run(self, obj, nparts: int, *, coords: np.ndarray | None = None,
            weights: np.ndarray | None = None) -> PartitionContext:
        """Partition a HexMesh or Graph; returns the full context.

        When tracing is on (``REPRO_OBS`` unset/on) the whole run happens
        inside one ``partition`` root span — ``ctx.trace`` — with one
        child span per stage; ``ctx.export_manifest()`` serializes it, and
        setting ``REPRO_OBS_DIR`` writes a manifest there automatically.
        """
        ctx = _make_context(obj, nparts, coords, weights)
        spectral = self.bisect.startswith("rsb")
        guard_on = _guard_enabled(self.guard)
        ctx.config = {"pre": self.pre, "bisect": self.bisect,
                      "post": list(self.post), "nparts": nparts, "n": ctx.n,
                      "guard": guard_on}
        if spectral:
            ctx.config["method"] = self.bisect_kw.get("method", "lanczos")

        root = obs.trace("partition", nparts=nparts, n=ctx.n,
                         pre=self.pre, bisect=self.bisect,
                         post=",".join(self.post), guard=guard_on)
        with root:
            if guard_on:
                self._run_guarded(ctx, nparts, spectral)
            else:
                self._run_stages(ctx, nparts, spectral)
        if isinstance(root, obs.Span):
            ctx.trace = root
            out_dir = os.environ.get("REPRO_OBS_DIR")
            if out_dir:
                ctx.export_manifest(runs_dir=out_dir)
        return ctx

    # -- the guarded path: validate → (components?) → stages → finalize --

    def _run_guarded(self, ctx: PartitionContext, nparts: int,
                     spectral: bool) -> None:
        policy = GuardPolicy.from_kw(self.guard_kw)
        greport = GuardReport()
        sites = tuple(self.guard_kw.get("chaos") or ())
        overlay = (chaos.overlay(
            sites, seed=int(self.guard_kw.get("chaos_seed", 0)),
            rate=float(self.guard_kw.get("chaos_rate", 1.0)))
            if sites else contextlib.nullcontext())
        with overlay:
            ncomp, comp = self._validate_input(ctx, nparts, policy, greport)
            if ncomp > 1:
                self._run_components(ctx, nparts, spectral, policy,
                                     greport, comp, ncomp)
            else:
                self._run_stages(ctx, nparts, spectral, policy=policy,
                                 greport=greport)
            self._finalize(ctx, nparts, policy, greport, ncomp)

    def _validate_input(self, ctx: PartitionContext, nparts: int,
                        policy: GuardPolicy, greport: GuardReport):
        """``guard:validate`` — the implicit first stage: typed
        :class:`GuardError` in strict mode, recorded repairs in sanitize
        mode, plus component detection (disconnected inputs are handled
        downstream, never rejected here)."""
        with obs.timed("guard:validate") as t:
            with obs.timed("validate"):
                validate_nparts(nparts, ctx.n)
                if ctx.mesh is not None:
                    mesh = ctx.mesh
                    if (ctx.coords is not mesh.coords
                            or ctx.weights is not mesh.weights):
                        mesh = dataclasses.replace(
                            mesh, coords=np.asarray(ctx.coords, np.float64),
                            weights=np.asarray(ctx.weights, np.float64))
                    mesh = validate_mesh(mesh, nparts=nparts,
                                         sanitize=policy.sanitize,
                                         report=greport)
                    ctx.mesh = mesh
                    ctx.coords, ctx.weights = mesh.coords, mesh.weights
                else:
                    g, c, w = validate_graph(
                        ctx.graph, coords=ctx.coords, weights=ctx.weights,
                        nparts=nparts, sanitize=policy.sanitize,
                        report=greport)
                    ctx.graph, ctx.coords, ctx.weights = g, c, w
            with obs.timed("dual_graph"):
                graph = ctx.require_graph()
            with obs.timed("components"):
                comp, ncomp = component_labels(graph)
            greport.components = max(greport.components, ncomp)
            if greport.sanitize_fixes:
                obs.counter_add("guard_sanitize_fixes",
                                greport.sanitize_fixes)
        ctx.config["components"] = ncomp
        ctx.stages.append(StageRecord(
            kind="guard", name="validate", seconds=t.seconds,
            info={"issues": len(greport.issues),
                  "fixes": greport.sanitize_fixes,
                  "components": ncomp},
        ))
        return ncomp, comp

    def _run_components(self, ctx: PartitionContext, nparts: int,
                        spectral: bool, policy: GuardPolicy,
                        greport: GuardReport, comp: np.ndarray,
                        ncomp: int) -> None:
        """Partition a disconnected input component by component.

        ``ncomp <= nparts``: largest-remainder part budgets per component,
        each component run through pre+bisect with its own budget; the
        post chain then runs ONCE over the full graph (no edge crosses
        components, so refinement can never merge them back).
        ``ncomp > nparts``: whole components are packed onto parts
        (greedy heaviest-first) — no bisection can improve on that without
        splitting a component across parts it shares no edge with.
        """
        w = np.ones(ctx.n) if ctx.weights is None else \
            np.asarray(ctx.weights, np.float64)
        comp_w = np.bincount(comp, weights=w, minlength=ncomp)
        with obs.timed(f"pre:{self.pre}") as t_pre:
            pass        # pre runs inside each component's sub-pipeline
        ctx.stages.append(StageRecord(
            kind="pre", name=self.pre, seconds=t_pre.seconds,
            info={"mode": "per-component", "components": ncomp}))

        parts = np.zeros(ctx.n, dtype=np.int64)
        merged = RSBReport(records=[], seconds=0.0, engine="-", pre=self.pre)
        with obs.timed(f"bisect:{self.bisect}") as t_bisect:
            if ncomp > nparts:
                parts = pack_components(comp_w, nparts)[comp]
                merged.engine = "pack-components"
                greport.degrade(f"input:packed-{ncomp}-components")
            else:
                budgets = proportional_budgets(comp_w, nparts)
                offset = 0
                for c in range(ncomp):
                    idx = np.flatnonzero(comp == c)
                    k = int(budgets[c])
                    if k <= 1 or idx.size <= 1:
                        parts[idx] = offset
                    else:
                        sub = _subset_context(ctx, idx, k)
                        self._run_stages(sub, k, spectral, policy=policy,
                                         greport=greport, with_post=False)
                        parts[idx] = offset + np.asarray(sub.parts,
                                                         np.int64)
                        for s in sub.stages:
                            s.info["component"] = c
                        ctx.stages.extend(sub.stages)
                        merged.records.extend(sub.report.records)
                        merged.engine = sub.report.engine
                    offset += k
        merged.seconds = t_bisect.seconds
        ctx.parts = parts
        ctx.parts_raw = parts.copy()
        ctx.report = merged
        ctx.stages.append(StageRecord(
            kind="bisect", name=self.bisect, seconds=t_bisect.seconds,
            info={"mode": ("pack" if ncomp > nparts else "per-component"),
                  "components": ncomp,
                  "iterations": merged.total_iterations}))

        if self.post:
            parts, agg, records = run_post_stages(
                ctx.require_graph(), ctx.parts, nparts, self.post,
                weights=ctx.weights, post_kw=self.post_kw)
            ctx.parts = parts
            ctx.stages.extend(records)
            merged.post = agg

    def _finalize(self, ctx: PartitionContext, nparts: int,
                  policy: GuardPolicy, greport: GuardReport,
                  ncomp: int) -> None:
        """``guard:finalize`` — the output-invariant closer.  Checks every
        run; *mutates* only when labels are structurally invalid or a
        degraded solve path left problems behind, so a healthy guarded run
        returns bit-identical labels to ``guard=False``."""
        with obs.timed("guard:finalize") as t:
            graph = ctx.require_graph()
            expected = max(0, ncomp - nparts)
            problems = check_output(
                graph, ctx.parts, nparts, weights=ctx.weights,
                balance_tol=policy.balance_tol,
                expected_disconnected=expected)
            structural = any(p.startswith("labels") for p in problems)
            degraded = bool(greport.fallbacks or greport.deadline_expired)
            enforced = False
            if structural or (problems and degraded):
                ctx.parts = enforce_output(
                    graph, ctx.parts, nparts, weights=ctx.weights,
                    balance_tol=policy.balance_tol, report=greport)
                enforced = True
                problems = check_output(
                    graph, ctx.parts, nparts, weights=ctx.weights,
                    balance_tol=policy.balance_tol,
                    expected_disconnected=expected)
        ctx.stages.append(StageRecord(
            kind="guard", name="finalize", seconds=t.seconds,
            info={"problems": list(problems), "enforced": enforced,
                  "retries": greport.retries,
                  "fallbacks": greport.fallbacks},
        ))
        if ctx.report is not None:
            ctx.report.guard = greport

    def _run_stages(self, ctx: PartitionContext, nparts: int,
                    spectral: bool, *, policy: GuardPolicy | None = None,
                    greport: GuardReport | None = None,
                    with_post: bool = True) -> None:
        # --- pre: reorder hint (rcb/rib) or one-shot permutation (sfc)
        with obs.timed(f"pre:{self.pre}") as t_pre:
            hint, order = None, None
            run_ctx = ctx
            if spectral and self.pre in ("rcb", "rib"):
                hint = self.pre  # per-level reorder, applied inside driver
            elif spectral and self.pre == "sfc":
                if ctx.coords is not None:
                    from repro.core.sfc import sfc_order

                    order = sfc_order(ctx.coords)
                    run_ctx = _permuted_input(ctx, order)
        ctx.stages.append(StageRecord(
            kind="pre", name=self.pre, seconds=t_pre.seconds,
            info={"mode": ("per-level" if hint else
                           "permute" if order is not None else "noop")},
        ))

        # --- bisect
        bkw = dict(self.bisect_kw)
        if policy is not None and spectral:
            bkw.setdefault("guard", policy)
        with obs.timed(f"bisect:{self.bisect}") as t_bisect:
            parts, report = _BISECT_STAGES[self.bisect](run_ctx, hint, **bkw)
        dt = t_bisect.seconds
        if order is not None:   # map labels back to the caller's order
            unperm = np.empty_like(parts)
            unperm[order] = parts
            parts = unperm
            if ctx.graph is None and run_ctx.graph is not None:
                # The bisect stage assembled the permuted dual graph; one
                # cheap CSR relabel recovers the caller-order graph, so the
                # post stages don't pay a second incidence-table assembly.
                ctx.graph = run_ctx.graph.sub(np.argsort(order))
        if report is None:
            report = RSBReport(records=[], seconds=dt, engine="-",
                               pre=self.pre)
        if greport is not None:
            _merge_guard(greport, report.guard)
        ctx.parts = np.asarray(parts, dtype=np.int64)
        ctx.parts_raw = ctx.parts.copy()
        ctx.report = report
        ctx.stages.append(StageRecord(
            kind="bisect", name=self.bisect, seconds=dt,
            info={"iterations": report.total_iterations},
        ))

        # --- post (one corridor per chain, fixed from the bisection's
        # part weights — see run_post_stages)
        if self.post and with_post:
            post_kw = dict(self.post_kw)
            if policy is not None and "guard" not in post_kw:
                # Stages that declare a ``guard`` keyword (the sharded
                # refinement pair) get the stage-deadline envelope; the
                # host stages simply never see it (_stage_kw filters).
                from repro.guard.policy import SolverGuard
                post_kw["guard"] = SolverGuard(
                    policy, seed=0, method="post", report=greport)
            parts, agg, records = run_post_stages(
                ctx.require_graph(), ctx.parts, nparts, self.post,
                weights=ctx.weights, post_kw=post_kw)
            ctx.parts = parts
            ctx.stages.extend(records)
            report.post = agg


# ---------------------------------------------------------------------------
# Front door (the classic keyword surface, now a pipeline builder)
# ---------------------------------------------------------------------------

_ENGINE_TO_BISECT = {"batched": "rsb-batched", "recursive": "rsb-recursive"}

# Explicit per-stage keyword routing: the old front door forwarded **kw
# blindly, silently dropping sfc's curve/bits and rcb/rib's everything.
_RSB_KW = {"method", "pre", "tol", "window", "max_restarts", "seed",
           "warm_start", "multilevel", "fine_restarts", "precond"}
_RSB_MESH_KW = _RSB_KW | {"laplacian"}
_RSB_GRAPH_KW = _RSB_KW | {"use_kernel"}
_GEOM_KW = {"rcb": set(), "rib": set(), "sfc": {"curve", "bits"},
            "random": {"seed"}}
_ML_KW = {"coarse_factor", "coarse_solver", "refine_passes", "stall",
          "coarse_passes", "seed", "max_levels", "min_coarsen_ratio"}

_REFINE_SPECS = {
    "none": (), "repair": ("repair",), "refine": ("refine",),
    "repair+refine": ("repair", "refine"),
    # Hill-climbing k-way FM (repro.core.kway): negative-gain prefixes with
    # rollback to the best prefix.  Greedy "repair+refine" stays the
    # default until the bench gate proves k-way ≥ greedy across suites.
    "kway": ("kway",), "repair+kway": ("repair", "kway"),
    # Device-resident sharded refinement (repro.dist.refine_sharded): one
    # boundary-label all_gather per sweep, Pallas segment-sum gain tables.
    "refine-sharded": ("refine-sharded",),
    "repair+refine-sharded": ("repair", "refine-sharded"),
    "kway-sharded": ("kway-sharded",),
    "repair+kway-sharded": ("repair", "kway-sharded"),
}


def parse_refine(refine) -> tuple:
    """``refine=`` spec → post-stage tuple ("none" is the escape hatch)."""
    if refine is None:
        return _REFINE_SPECS["repair+refine"]
    if isinstance(refine, str):
        try:
            return _REFINE_SPECS[refine]
        except KeyError:
            raise ValueError(
                f"unknown refine spec: {refine!r} "
                f"(have {tuple(_REFINE_SPECS)} or a stage tuple)") from None
    return tuple(refine)


def _check_kw(kw: dict, allowed: set, who: str) -> None:
    unknown = set(kw) - allowed
    if unknown:
        raise TypeError(
            f"unknown keyword(s) for partitioner {who!r}: "
            f"{sorted(unknown)} (allowed: {sorted(allowed)})")


def partition(
    obj,
    nparts: int,
    *,
    partitioner: str = "rsb",
    coords: np.ndarray | None = None,
    weights: np.ndarray | None = None,
    engine: str = "batched",
    refine: str | tuple | None = None,
    refine_sweeps: int = 4,
    balance_tol: float = 0.05,
    guard: bool | None = None,
    guard_kw: dict | None = None,
    **kw,
) -> np.ndarray:
    """Uniform front door: partitioner ∈ {rsb, rsb_inverse, multilevel,
    rcb, rib, sfc, random}, built as a :class:`PartitionPipeline` run.

    ``refine`` selects the post stages: "repair+refine" (the default for
    the RSB family — parRSB ships repaired/smoothed labels, not raw
    bisections), "repair+kway" (hill-climbing k-way FM), "repair",
    "refine", "kway", "none", or an explicit stage tuple.
    Geometric/random baselines default to "none" so they stay raw
    comparison points; pass ``refine=`` explicitly to post-process them.
    ``refine_sweeps``/``balance_tol`` parameterize the post stages.

    ``engine`` selects the RSB driver ("batched"/"recursive"); remaining
    keywords are routed to the selected stage and unknown keys raise.
    ``guard``/``guard_kw`` switch and parameterize the fault-tolerance
    envelope (validation, solver escalation, output finalizer — see
    :class:`PartitionPipeline`); the default defers to ``REPRO_GUARD``.
    Use :meth:`PartitionPipeline.run` directly to get the full context
    (report with post section, per-stage timings) instead of labels only.
    """
    is_mesh = hasattr(obj, "vert_gid")
    post_kw = dict(sweeps=refine_sweeps, balance_tol=balance_tol)
    gkw = dict(guard=guard, guard_kw=dict(guard_kw or {}))

    if partitioner in ("rsb", "rsb_lanczos", "rsb_inverse"):
        if engine not in _ENGINE_TO_BISECT:
            raise ValueError(f"unknown engine: {engine}")
        if partitioner == "rsb_inverse":
            kw["method"] = "inverse"
        _check_kw(kw, _RSB_MESH_KW if is_mesh else _RSB_GRAPH_KW, partitioner)
        pre = kw.pop("pre", "rcb")
        pipe = PartitionPipeline(
            pre=pre or "none", bisect=_ENGINE_TO_BISECT[engine],
            post=parse_refine(refine), bisect_kw=kw, post_kw=post_kw, **gkw,
        )
    elif partitioner == "multilevel":
        # The V-cycle's default post chain is repair+kway: its bisect cost
        # is so small that the deeper hill-climbing chain is free by
        # comparison, and the V-cycle's own per-level sweeps are bounded
        # (boundary-only, stall-capped) rather than exhaustive.
        _check_kw(kw, _ML_KW, partitioner)
        pipe = PartitionPipeline(
            pre="none", bisect="multilevel",
            post=parse_refine("repair+kway" if refine is None else refine),
            bisect_kw=dict(balance_tol=balance_tol, **kw), post_kw=post_kw,
            **gkw,
        )
    elif partitioner in _GEOM_KW:
        _check_kw(kw, _GEOM_KW[partitioner], partitioner)
        pipe = PartitionPipeline(
            pre="none", bisect=partitioner,
            post=parse_refine("none" if refine is None else refine),
            bisect_kw=kw, post_kw=post_kw, **gkw,
        )
    else:
        raise ValueError(f"unknown partitioner: {partitioner}")

    return pipe.run(obj, nparts, coords=coords, weights=weights).parts
