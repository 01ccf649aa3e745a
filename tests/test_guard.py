"""Adversarial suite for the fault-tolerance guard (repro.guard).

No `hypothesis` in this container, so the property tests are a seeded
harness: every case is parametrized over seeds and generates its
pathological input from that seed's rng — same coverage style
(generate → assert invariant), fully deterministic replays.

The contract under test, end to end: a pathological input fed to ANY
pipeline preset either raises a typed :class:`GuardError` (strict mode)
or comes back as a full-coverage labeling — and when the preset's post
chain includes "repair", a connected one.
"""

import dataclasses

import numpy as np
import pytest

from repro.configs.parrsb import PIPELINE_PRESETS, make_pipeline, make_smoke_config
from repro.core.fiedler import FiedlerResult
from repro.core.pipeline import PartitionPipeline
from repro.core.rsb import _node_seed
from repro.guard import (
    GuardError,
    GuardPolicy,
    GuardReport,
    SolverBreakdown,
    SolverGuard,
    chaos,
    check_output,
    check_positive_int,
    component_labels,
    count_disconnected,
    enforce_output,
    failure_reason,
    fallback_vector,
    pack_components,
    proportional_budgets,
    validate_graph,
    validate_mesh,
    validate_nparts,
)
from repro.mesh import box_mesh, grid_graph_2d
from repro.mesh.graphs import build_csr

SEEDS = [0, 1, 2, 3, 4]


def _graph_with(n=36, *, rng, self_loops=0, dup_edges=0, bad_w=0,
                neg_w=0):
    """A connected 6x6 grid graph with injected defects, as raw COO fed
    through a non-coalescing CSR build (build_csr would repair them)."""
    g = grid_graph_2d(6, 6)
    src, dst, w = [g.rows], [g.indices], [np.asarray(g.weights, float)]
    if self_loops:
        nodes = rng.choice(n, self_loops, replace=False)
        src.append(nodes)
        dst.append(nodes)
        w.append(np.ones(self_loops))
    if dup_edges:
        pick = rng.choice(g.rows.size, dup_edges, replace=False)
        src.append(g.rows[pick])
        dst.append(g.indices[pick])
        w.append(np.ones(dup_edges))
    src, dst = np.concatenate(src), np.concatenate(dst)
    w = np.concatenate(w)
    if bad_w:
        w[rng.choice(w.size, bad_w, replace=False)] = np.nan
    if neg_w:
        w[rng.choice(w.size, neg_w, replace=False)] = -1.0
    order = np.argsort(src, kind="stable")
    indptr = np.searchsorted(src[order], np.arange(n + 1))
    return dataclasses.replace(g, indptr=indptr, indices=dst[order],
                               weights=w[order])


def _two_component_graph(side=6):
    g = grid_graph_2d(side, side)
    n = g.n
    src = np.concatenate([g.rows, g.rows + n])
    dst = np.concatenate([g.indices, g.indices + n])
    w = np.concatenate([g.weights, g.weights])
    return build_csr(src, dst, 2 * n, weights=w, symmetrize=False)


# ---------------------------------------------------------------------------
# Scalar / CLI front door
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", ["x", -1, 0, 2.5, None, float("nan")])
def test_check_positive_int_rejects(bad):
    with pytest.raises(GuardError) as ei:
        check_positive_int("count", bad)
    assert ei.value.code == "bad-argument"
    assert "count" in ei.value.diagnostic()


def test_check_positive_int_accepts():
    assert check_positive_int("count", "7") == 7
    assert check_positive_int("count", 3.0, maximum=3) == 3
    with pytest.raises(GuardError):
        check_positive_int("count", 4, maximum=3)


def test_validate_nparts_range():
    assert validate_nparts("4", 10) == 4
    for bad in (0, 11, "x", None):
        with pytest.raises(GuardError) as ei:
            validate_nparts(bad, 10)
        assert ei.value.code == "bad-nparts"


# ---------------------------------------------------------------------------
# Graph/mesh validation: strict raises typed, sanitize repairs + records
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("defect,code", [
    (dict(self_loops=3), "self-loop"),
    (dict(dup_edges=4), "duplicate-edge"),
    (dict(bad_w=2), "nonfinite-edge-weight"),
    (dict(neg_w=2), "nonpositive-edge-weight"),
])
def test_validate_graph_strict_vs_sanitize(seed, defect, code):
    rng = np.random.default_rng(seed)
    g = _graph_with(rng=rng, **defect)
    with pytest.raises(GuardError) as ei:
        validate_graph(g)
    assert ei.value.code == code

    report = GuardReport()
    g2, _, _ = validate_graph(g, sanitize=True, report=report)
    assert report.sanitize_fixes > 0
    assert any(i.code == code and i.fixed for i in report.issues)
    # the sanitized rebuild is defect-free
    validate_graph(g2)
    assert np.all(np.isfinite(g2.weights)) and np.all(g2.weights > 0)
    assert not np.any(g2.rows == g2.indices)


@pytest.mark.parametrize("seed", SEEDS)
def test_validate_graph_node_data(seed):
    rng = np.random.default_rng(seed)
    g = grid_graph_2d(6, 6)
    w = np.ones(36)
    w[rng.choice(36, 3, replace=False)] = np.nan
    c = rng.random((36, 2))
    c[rng.choice(36, 2, replace=False)] = np.inf
    with pytest.raises(GuardError):
        validate_graph(g, weights=w)
    with pytest.raises(GuardError):
        validate_graph(g, coords=c)
    _, c2, w2 = validate_graph(g, coords=c, weights=w, sanitize=True,
                               report=GuardReport())
    assert np.all(np.isfinite(c2)) and np.all(np.isfinite(w2))
    assert np.all(w2 > 0)


def test_validate_graph_malformed_csr_never_repairable():
    g = grid_graph_2d(4, 4)
    bad = dataclasses.replace(g, indptr=g.indptr[:-1].copy())
    for sanitize in (False, True):
        with pytest.raises(GuardError) as ei:
            validate_graph(bad, sanitize=sanitize)
        assert ei.value.code == "malformed-csr"


def test_validate_mesh_patches(box443):
    coords = np.asarray(box443.coords).copy()
    coords[5] = np.nan
    weights = np.asarray(box443.weights, float).copy()
    weights[7] = -3.0
    bad = dataclasses.replace(box443, coords=coords, weights=weights)
    with pytest.raises(GuardError):
        validate_mesh(bad)
    report = GuardReport()
    fixed = validate_mesh(bad, sanitize=True, report=report)
    assert np.all(np.isfinite(fixed.coords))
    assert np.all(np.asarray(fixed.weights, float) >= 0)
    assert report.sanitize_fixes == 2


def test_zero_degree_nodes_recorded_not_raised():
    g = grid_graph_2d(4, 4)
    # node-induced graph on 18 nodes where 2 have no edges
    g18 = build_csr(g.rows, g.indices, 18, weights=g.weights,
                    symmetrize=False)
    report = GuardReport()
    validate_graph(g18, report=report)          # strict mode: no raise
    assert any(i.code == "zero-degree-node" for i in report.issues)
    _, ncomp = component_labels(g18)
    assert ncomp == 3                           # grid + two singletons


# ---------------------------------------------------------------------------
# Component budgets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_proportional_budgets_properties(seed):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 8))
    nparts = int(rng.integers(k, 40))
    w = rng.random(k) * rng.integers(1, 100)
    b = proportional_budgets(w, nparts)
    assert b.sum() == nparts and b.min() >= 1
    # proportionality: a component's budget is within 1 of its fair share
    # (largest-remainder), up to the floor-of-one distortion
    fair = nparts * w / w.sum()
    assert np.all(b >= np.minimum(1, np.ceil(fair)))
    assert np.all(np.abs(b - np.maximum(fair, 1)) <= k)


def test_proportional_budgets_rejects_too_few_parts():
    with pytest.raises(GuardError) as ei:
        proportional_budgets([1.0, 1.0, 1.0], 2)
    assert ei.value.code == "bad-nparts"


@pytest.mark.parametrize("seed", SEEDS)
def test_pack_components_properties(seed):
    rng = np.random.default_rng(seed)
    nparts = int(rng.integers(2, 6))
    k = int(rng.integers(nparts + 1, 40))
    w = rng.random(k)
    group = pack_components(w, nparts)
    assert group.shape == (k,)
    assert set(np.unique(group)) == set(range(nparts))   # no empty bin
    loads = np.bincount(group, weights=w, minlength=nparts)
    # greedy heaviest-first bound: max bin ≤ mean + heaviest item
    assert loads.max() <= w.sum() / nparts + w.max() + 1e-12


# ---------------------------------------------------------------------------
# Solver guard: health checks + the escalation ladder
# ---------------------------------------------------------------------------

def _res(vec, lam=0.1, residual=1e-6, breakdown=False):
    return FiedlerResult(vector=np.asarray(vec, float), eigenvalue=lam,
                         residual=residual, iterations=3, method="lanczos",
                         breakdown=breakdown)


def test_failure_reason_taxonomy():
    good = np.linspace(-1, 1, 8)
    assert failure_reason(None, 8) == "exception"
    assert failure_reason(_res(good, breakdown=True), 8) == "breakdown"
    v = good.copy()
    v[3] = np.nan
    assert failure_reason(_res(v), 8) == "nonfinite-vector"
    assert failure_reason(_res(good, lam=np.nan), 8) == "nonfinite-eigenpair"
    assert failure_reason(_res(np.zeros(8)), 8) == "degenerate-vector"
    assert failure_reason(_res(good, lam=1e-9, residual=1.0), 8) \
        == "stalled-residual"
    assert failure_reason(_res(good), 8) is None
    # a 1-node problem cannot be "degenerate"
    assert failure_reason(_res(np.zeros(1)), 1) is None


def test_fallback_vector_prefers_longest_axis():
    coords = np.stack([np.linspace(0, 1, 10), np.linspace(0, 5, 10)], 1)
    np.testing.assert_allclose(fallback_vector(10, coords), coords[:, 1])
    np.testing.assert_allclose(fallback_vector(4), np.arange(4.0))
    # degenerate coords (zero span) fall back to the index ramp
    np.testing.assert_allclose(fallback_vector(4, np.zeros((4, 3))),
                               np.arange(4.0))


def _ladder(policy, script, method="lanczos", seed=0):
    """Run one rescue through a scripted solve_fn.  ``script`` maps attempt
    index (in call order) to a result; missing entries raise."""
    calls = []

    def solve_fn(m, s):
        calls.append((m, s))
        i = len(calls) - 1
        if i in script:
            return script[i]
        raise SolverBreakdown("scripted failure")

    sg = SolverGuard(policy, seed=seed, method=method)
    res, why = sg.admit(_res(np.zeros(16)), level=0, p_lo=0, size=16)
    assert why == "degenerate-vector"
    out = sg.rescue(solve_fn, why, level=0, p_lo=0, size=16)
    return sg, out, calls


def test_ladder_retry_succeeds():
    good = _res(np.linspace(-1, 1, 16))
    sg, out, calls = _ladder(GuardPolicy(max_retries=2), {0: good})
    assert out is good
    assert sg.report.retries == 1 and sg.report.fallbacks == 0
    assert calls[0][0] == "lanczos"          # retried with primary method
    assert calls[0][1] == _node_seed(0, 0, 0, 1)   # attempt-keyed seed


def test_ladder_switch_succeeds():
    good = _res(np.linspace(-1, 1, 16))
    sg, out, calls = _ladder(GuardPolicy(max_retries=1), {1: good})
    assert out is good
    assert sg.report.retries == 1 and sg.report.fallbacks == 1
    assert calls[1][0] == "inverse"          # switched family
    assert any("switched-to-inverse" in d for d in sg.report.degraded)


def test_ladder_exhausts_to_fallback():
    sg, out, calls = _ladder(GuardPolicy(max_retries=2), {})
    assert out.method == "fallback-index" and out.breakdown
    assert float(np.ptp(out.vector)) > 0     # still splittable
    assert sg.report.retries == 2 and sg.report.fallbacks == 2
    assert [m for m, _ in calls] == ["lanczos", "lanczos", "inverse"]


def test_ladder_no_switch_policy():
    sg, out, calls = _ladder(
        GuardPolicy(max_retries=1, switch_method=False), {})
    assert out.method == "fallback-index"
    assert [m for m, _ in calls] == ["lanczos"]


def test_ladder_lets_non_breakdown_errors_escape():
    """Only a SolverBreakdown is a failed attempt: a compile, runtime or
    out-of-memory error from a solve reaches the caller."""
    sg = SolverGuard(GuardPolicy(max_retries=2), seed=0, method="lanczos")

    def solve_fn(m, s):
        raise RuntimeError("device failure")

    with pytest.raises(RuntimeError, match="device failure"):
        sg.rescue(solve_fn, "breakdown", level=0, p_lo=0, size=16)
    assert sg.report.fallbacks == 0


@pytest.mark.parametrize("engine", ["recursive", "batched"])
def test_engine_solve_errors_reach_the_caller(engine, monkeypatch):
    """A device error in the first solve of a guarded RSB run propagates
    instead of degrading to the fallback vector."""
    import repro.core.rsb as rsb

    def boom(*a, **k):
        raise RuntimeError("device failure")

    monkeypatch.setattr(rsb, "fiedler_from_graph", boom)
    monkeypatch.setattr(rsb, "fiedler_from_graph_batched", boom)
    with pytest.raises(RuntimeError, match="device failure"):
        rsb.rsb_partition_graph(grid_graph_2d(8, 8), 4, engine=engine,
                                guard=GuardPolicy())


def test_deadline_skips_straight_to_fallback():
    sg = SolverGuard(GuardPolicy(max_retries=5, deadline=0.0), seed=0,
                     method="lanczos")
    import time
    time.sleep(0.01)
    assert sg.expired()
    out = sg.rescue(lambda m, s: pytest.fail("must not re-solve"),
                    "breakdown", level=0, p_lo=0, size=8)
    assert out.method == "fallback-index"
    assert sg.report.deadline_expired
    assert sg.report.retries == 0 and sg.report.fallbacks == 1


# ---------------------------------------------------------------------------
# Deterministic seeds & chaos
# ---------------------------------------------------------------------------

def test_node_seed_attempt_determinism():
    base = _node_seed(7, 2, 5)
    assert base == _node_seed(7, 2, 5, 0)       # attempt=0 is bit-parity
    seen = {_node_seed(7, 2, 5, a) for a in range(6)}
    assert len(seen) == 6                       # attempts never collide
    assert _node_seed(7, 2, 5, 3) == _node_seed(7, 2, 5, 3)


def test_chaos_should_fire_deterministic():
    with chaos.overlay(("solver_nan",), seed=3, rate=0.5):
        draws = [chaos.should_fire("solver_nan", 0, i) for i in range(200)]
        assert draws == [chaos.should_fire("solver_nan", 0, i)
                         for i in range(200)]
        assert 0 < sum(draws) < 200             # rate actually subsamples
        assert not chaos.should_fire("empty_split", 0, 0)  # not enabled
    assert not chaos.active()                   # overlay restored


def test_chaos_suppressed_and_unknown_site():
    with chaos.overlay(("deadline",)):
        assert chaos.enabled("deadline")
        with chaos.suppressed():
            assert not chaos.enabled("deadline")
            assert not chaos.should_fire("deadline")
        assert chaos.enabled("deadline")
    with pytest.raises(ValueError):
        chaos.configure(("not-a-site",))


# ---------------------------------------------------------------------------
# Breakdown flag surfacing (batched + recursive inverse iteration)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["recursive", "batched"])
def test_cg_divergence_sets_breakdown_record(grid16, engine):
    from repro.core.rsb import rsb_partition_graph

    with chaos.overlay(("cg_divergence",)):
        parts, report = rsb_partition_graph(
            grid16, 2, method="inverse", engine=engine)
    # no guard: the breakdown must still surface per bisection record
    # (grid16 is 256 nodes — above the dense cutoff, so inverse runs)
    assert any(r.breakdown for r in report.records)
    assert parts.shape == (grid16.n,)


# ---------------------------------------------------------------------------
# Pipeline integration: guard on/off parity, component dispatch, chaos e2e
# ---------------------------------------------------------------------------

def test_guard_on_off_parity(grid16):
    kw = dict(pre="none", bisect="rsb-batched", post=("repair", "refine"))
    on = PartitionPipeline(guard=True, **kw).run(grid16, 4)
    off = PartitionPipeline(guard=False, **kw).run(grid16, 4)
    np.testing.assert_array_equal(on.parts, off.parts)
    assert on.report.guard is not None and on.report.guard.clean
    assert off.report.guard is None
    assert on.config["guard"] and not off.config["guard"]


def test_guard_env_switch(grid16, monkeypatch):
    monkeypatch.setenv("REPRO_GUARD", "off")
    ctx = PartitionPipeline(pre="none", bisect="rsb-batched").run(grid16, 2)
    assert not ctx.config["guard"] and ctx.report.guard is None
    monkeypatch.setenv("REPRO_GUARD", "on")
    ctx = PartitionPipeline(pre="none", bisect="rsb-batched").run(grid16, 2)
    assert ctx.config["guard"] and ctx.report.guard is not None


def test_two_components_proportional(seed=0):
    g = _two_component_graph(6)                  # two equal 36-node grids
    ctx = PartitionPipeline(pre="none", bisect="rsb-batched",
                            post=("repair", "refine"),
                            guard=True).run(g, 4)
    assert ctx.report.guard.components == 2
    assert count_disconnected(g, ctx.parts, 4) == 0
    counts = np.bincount(ctx.parts, minlength=4)
    assert counts.min() > 0
    # no part spans both components
    comp = np.repeat([0, 1], 36)
    for p in range(4):
        assert np.unique(comp[ctx.parts == p]).size == 1


def test_more_components_than_parts_packs():
    # 12 disjoint edges → 12 components, packed onto 3 parts
    src = np.arange(0, 24, 2)
    gp = build_csr(np.concatenate([src, src + 1]),
                   np.concatenate([src + 1, src]), 24, symmetrize=False)
    ctx = PartitionPipeline(pre="none", bisect="rsb-batched",
                            post=("repair",), guard=True).run(gp, 3)
    assert sorted(np.unique(ctx.parts)) == [0, 1, 2]
    assert any("packed" in d for d in ctx.report.guard.degraded)
    counts = np.bincount(ctx.parts, minlength=3)
    assert counts.max() <= 10                    # greedy-packing balance


@pytest.mark.parametrize("site", ["solver_nan", "empty_split", "deadline"])
def test_chaos_end_to_end(grid16, site):
    ctx = PartitionPipeline(pre="none", bisect="rsb-batched",
                            post=("repair", "refine"), guard=True,
                            guard_kw={"chaos": (site,)}).run(grid16, 4)
    gr = ctx.report.guard
    assert gr.fallbacks > 0
    assert sorted(np.unique(ctx.parts)) == [0, 1, 2, 3]
    assert count_disconnected(grid16, ctx.parts, 4) == 0
    if site == "deadline":
        assert gr.deadline_expired


def test_chaos_runs_are_deterministic(grid16):
    kw = dict(pre="none", bisect="rsb-batched", post=("repair", "refine"),
              guard=True, guard_kw={"chaos": ("solver_nan",)})
    a = PartitionPipeline(**kw).run(grid16, 4)
    b = PartitionPipeline(**kw).run(grid16, 4)
    np.testing.assert_array_equal(a.parts, b.parts)
    assert a.report.guard.fallbacks == b.report.guard.fallbacks


# ---------------------------------------------------------------------------
# Output invariant: check + graceful-degradation closer
# ---------------------------------------------------------------------------

def test_check_output_taxonomy(grid16):
    n = grid16.n
    good = (np.arange(n) // (n // 4)).clip(0, 3)
    assert check_output(grid16, good, 4) == []
    assert check_output(grid16, None, 4) == ["labels-missing"]
    assert check_output(grid16, good[:-1], 4) == ["labels-missing"]
    assert check_output(grid16, good.astype(float), 4) \
        == ["labels-not-integer"]
    assert any("out-of-range" in p
               for p in check_output(grid16, good + 7, 4))
    frag = good.copy()
    frag[0] = 3                                  # corner detached from part 3
    assert any("disconnected" in p for p in check_output(grid16, frag, 4))


@pytest.mark.parametrize("seed", SEEDS)
def test_enforce_output_from_garbage(grid16, seed):
    rng = np.random.default_rng(seed)
    garbage = rng.integers(-5, 9, grid16.n)      # out-of-range labels
    report = GuardReport()
    parts = enforce_output(grid16, garbage, 4, report=report)
    assert check_output(grid16, parts, 4) == []
    assert report.fallbacks >= 1
    assert any("finalize" in d for d in report.degraded)
    # idempotent on a now-valid labeling
    again = enforce_output(grid16, parts, 4, report=GuardReport())
    np.testing.assert_array_equal(parts, again)


def test_enforce_output_none_labels(grid16):
    parts = enforce_output(grid16, None, 4, report=GuardReport())
    assert check_output(grid16, parts, 4) == []


# ---------------------------------------------------------------------------
# Halo plan self-check
# ---------------------------------------------------------------------------

def test_halo_truncate_detected_and_rebuilt(grid16):
    from repro.dist import plan_halo_sharding, verify_halo_plan
    from repro.dist.partition_aware import _truncate_exports

    parts = (np.arange(grid16.n) // (grid16.n // 4)).clip(0, 3)
    clean = plan_halo_sharding(grid16, parts, 4)
    assert verify_halo_plan(clean) == []
    assert verify_halo_plan(_truncate_exports(clean)) != []
    with chaos.overlay(("halo_truncate",)):
        rebuilt = plan_halo_sharding(grid16, parts, 4)
    assert verify_halo_plan(rebuilt) == []
    np.testing.assert_array_equal(rebuilt.export_mask, clean.export_mask)


# ---------------------------------------------------------------------------
# The preset sweep: every PIPELINE_PRESETS entry absorbs pathological input
# ---------------------------------------------------------------------------

def _pathological_mesh(kind, seed):
    rng = np.random.default_rng(seed)
    m = box_mesh(4, 4, 3)
    coords = np.asarray(m.coords).copy()
    weights = np.asarray(m.weights, float).copy()
    if kind == "nan-coords":
        coords[rng.choice(m.nelems, 3, replace=False)] = np.nan
    elif kind == "bad-weights":
        weights[rng.choice(m.nelems, 3, replace=False)] = np.nan
        weights[rng.choice(m.nelems, 2, replace=False)] = -2.0
    return dataclasses.replace(m, coords=coords, weights=weights)


@pytest.mark.parametrize("preset", sorted(PIPELINE_PRESETS))
@pytest.mark.parametrize("kind", ["nan-coords", "bad-weights"])
def test_presets_strict_mode_raises_typed(preset, kind):
    mesh = _pathological_mesh(kind, seed=0)
    pipe = make_pipeline(preset, config=make_smoke_config(), guard=True)
    with pytest.raises(GuardError):
        pipe.run(mesh, 4)


@pytest.mark.parametrize("seed", SEEDS[:3])
@pytest.mark.parametrize("preset", sorted(PIPELINE_PRESETS))
def test_presets_sanitize_mode_upholds_invariant(preset, seed):
    kind = ["nan-coords", "bad-weights"][seed % 2]
    mesh = _pathological_mesh(kind, seed)
    pipe = make_pipeline(preset, config=make_smoke_config(), guard=True,
                         guard_kw={"sanitize": True})
    ctx = pipe.run(mesh, 4)
    gr = ctx.report.guard
    assert gr is not None and gr.validated and gr.sanitize_fixes > 0
    parts = np.asarray(ctx.parts)
    assert parts.shape == (mesh.nelems,)
    assert parts.min() >= 0 and parts.max() < 4      # always-valid labels
    if "repair" in PIPELINE_PRESETS[preset]["post"]:
        # full invariant only where the chain contains the repairer
        assert count_disconnected(ctx.require_graph(), parts, 4) == 0
        assert sorted(np.unique(parts)) == [0, 1, 2, 3]


@pytest.mark.parametrize("preset", sorted(PIPELINE_PRESETS))
def test_presets_disconnected_graph(preset):
    """A two-component dual-graph analogue through every preset: handled
    via per-component dispatch, never a crash."""
    g = _two_component_graph(4)                      # 2 × 16 nodes
    coords = np.concatenate([
        np.mgrid[0:4, 0:4].reshape(2, -1).T,
        np.mgrid[0:4, 0:4].reshape(2, -1).T + 100.0]).astype(float)
    pipe = make_pipeline(preset, config=make_smoke_config(), guard=True)
    ctx = pipe.run(g, 2, coords=coords)
    assert ctx.report.guard.components == 2
    parts = np.asarray(ctx.parts)
    assert parts.shape == (g.n,) and parts.min() >= 0 and parts.max() < 2
    if "repair" in PIPELINE_PRESETS[preset]["post"]:
        assert count_disconnected(g, parts, 2) == 0
