"""repro.obs contract tests: span nesting, the disabled fast path,
metric merge semantics, manifest round-trip, and REPRO_OBS=off parity
(the pipeline must be bit-for-bit identical with tracing off)."""

import json
import os
import pickle

import numpy as np
import pytest

from repro import obs
from repro.core import PartitionPipeline
from repro.dist.partition_aware import plan_halo_sharding
from repro.mesh import box_mesh, dual_graph, pebble_mesh


@pytest.fixture(autouse=True)
def _obs_on():
    """Every test starts with tracing on and an empty span stack."""
    prev = obs.set_enabled(True)
    yield
    obs.set_enabled(prev)


# ---------------------------------------------------------------------------
# Span tree: nesting, ordering, timing
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    with obs.trace("root", run=1) as root:
        with obs.span("a"):
            obs.counter_add("hits", 2)
            with obs.span("a1"):
                pass
            with obs.span("a2"):
                pass
        with obs.span("b"):
            pass
    assert [c.name for c in root.children] == ["a", "b"]
    a = root.find("a")
    assert [c.name for c in a.children] == ["a1", "a2"]
    assert a.counters == {"hits": 2.0}
    assert root.tags == {"run": 1}
    # pre-order walk
    assert [s.name for s in root.walk()] == ["root", "a", "a1", "a2", "b"]
    # children nest inside the parent's time window
    assert a.t0 >= root.t0 and a.t1 <= root.t1 + 1e-9
    assert root.seconds >= a.seconds


def test_timed_measures_inside_and_outside_traces():
    with obs.trace("root") as root:
        with obs.timed("work") as t:
            pass
        assert isinstance(t, obs.Span)
    assert root.find("work") is t
    # outside any trace: a plain timer, nothing recorded anywhere
    with obs.timed("loose") as t2:
        pass
    assert not isinstance(t2, obs.Span)
    assert t2.seconds >= 0.0


def test_exception_pops_span_stack():
    with pytest.raises(RuntimeError):
        with obs.trace("root"):
            with obs.span("inner"):
                raise RuntimeError("boom")
    assert obs.current_span() is None


# ---------------------------------------------------------------------------
# Disabled mode: the zero-allocation fast path
# ---------------------------------------------------------------------------

def test_disabled_span_is_shared_noop_singleton():
    with obs.disabled():
        s1 = obs.span("x")
        s2 = obs.span("y", tag=1)
        assert s1 is obs.NOOP_SPAN and s2 is obs.NOOP_SPAN
        with s1:
            pass
        assert obs.current_span() is None
        # trace/timed degrade to timers that still measure wall time
        with obs.trace("root") as t:
            pass
        assert not isinstance(t, obs.Span)
        obs.counter_add("nope")          # must not raise, must not record
        obs.gauge_set("nope", 1)
        obs.gauge_max("nope", 1)
    # span() outside any trace is also the no-op singleton (enabled mode)
    assert obs.span("loose") is obs.NOOP_SPAN


# ---------------------------------------------------------------------------
# Counter / gauge merge semantics
# ---------------------------------------------------------------------------

def test_counters_sum_over_subtree():
    with obs.trace("root") as root:
        obs.counter_add("fm_moves", 3)
        with obs.span("child"):
            obs.counter_add("fm_moves", 4)
    assert root.total_counters()["fm_moves"] == 7.0


def test_gauge_aggregation_follows_registry():
    # residual_max/amg_levels are max-gauges, edge_cut is last-write
    with obs.trace("root") as root:
        obs.gauge_max("residual_max", 0.5)
        obs.gauge_set("edge_cut", 100.0)
        with obs.span("child"):
            obs.gauge_max("residual_max", 0.2)
            obs.gauge_set("edge_cut", 80.0)
            obs.gauge_set("amg_levels", 4)
    total = root.total_counters()
    assert total["residual_max"] == 0.5      # max over subtree
    assert total["edge_cut"] == 80.0         # last write wins
    assert total["amg_levels"] == 4


def test_gauge_max_within_one_span():
    with obs.trace("root") as root:
        obs.gauge_max("residual_max", 0.1)
        obs.gauge_max("residual_max", 0.3)
        obs.gauge_max("residual_max", 0.2)
    assert root.gauges["residual_max"] == 0.3


def test_merge_metrics_unregistered_defaults():
    # unregistered counters sum; unregistered gauges default to max
    dst = {}
    obs.merge_metrics(dst, {"custom": 1.0}, kind="counter")
    obs.merge_metrics(dst, {"custom": 2.0}, kind="counter")
    assert dst["custom"] == 3.0
    g = {}
    obs.merge_metrics(g, {"g": 1.0}, kind="gauge")
    obs.merge_metrics(g, {"g": 0.5}, kind="gauge")
    assert g["g"] == 1.0


# ---------------------------------------------------------------------------
# Manifest round-trip + validation
# ---------------------------------------------------------------------------

def test_manifest_round_trip(tmp_path):
    with obs.trace("partition", nparts=4) as root:
        with obs.span("bisect:rsb-batched"):
            obs.counter_add("fiedler_solves", 3)
            obs.gauge_set("amg_levels", 2)
    path = str(tmp_path / "run.jsonl")
    config = {"pre": "none", "bisect": "rsb-batched", "post": []}
    obs.write_manifest(root, path, name="t", config=config)
    header, loaded = obs.load_manifest(path)
    assert header["schema"] == obs.SCHEMA
    assert header["config"] == config
    assert header["totals"]["metrics"]["fiedler_solves"] == 3.0
    assert [s.name for s in loaded.walk()] == [s.name for s in root.walk()]
    b = loaded.find("bisect:rsb-batched")
    assert b.counters == {"fiedler_solves": 3.0}
    assert b.gauges == {"amg_levels": 2}
    assert loaded.tags == {"nparts": 4}
    assert abs(loaded.seconds - root.seconds) < 1e-9
    # every line is valid JSON (it is a JSONL file, not a JSON file)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_validate_manifest_flags_missing_stage_span(tmp_path):
    with obs.trace("partition") as root:
        with obs.span("pre:rcb"):
            pass
    path = str(tmp_path / "bad.jsonl")
    obs.write_manifest(root, path, name="t", config={
        "pre": "rcb", "bisect": "rsb-batched", "post": ["repair"]})
    problems = obs.validate_manifest(path)
    missing = {p.split("'")[1] for p in problems if "missing span" in p}
    assert missing == {"bisect:rsb-batched", "solve", "split", "warm_start",
                       "reorder", "sub", "post:repair"}


def test_expected_span_names_from_config():
    names = obs.expected_span_names(
        {"pre": "none", "bisect": "rcb", "post": ["repair", "kway"]})
    assert names == {"partition", "bisect:rcb", "post:repair", "post:kway"}
    # A guarded single-component rsb-batched run above the dense cutoff
    # needs every host step of the level loop and of the batched solve.
    default = {"pre": "rcb", "bisect": "rsb-batched", "post": ["repair"],
               "n": 4096, "guard": True, "components": 1,
               "method": "lanczos"}
    assert obs.expected_span_names(default) == {
        "partition", "guard:validate", "validate", "dual_graph",
        "components", "guard:finalize", "pre:rcb", "bisect:rsb-batched",
        "solve", "split", "warm_start", "reorder", "sub", "pack",
        "restarts", "post:repair"}
    # No geometric pre stage: no reorder or relabel.  Inverse iteration,
    # or a root at the dense cutoff: no packed Lanczos solve.
    for change, gone in (({"pre": "none"}, {"pre:rcb", "reorder", "sub"}),
                         ({"method": "inverse"}, {"pack", "restarts"}),
                         ({"n": 192}, {"pack", "restarts"}),
                         ({"components": 2}, {
                             "solve", "split", "warm_start", "reorder",
                             "sub", "pack", "restarts"})):
        names = obs.expected_span_names({**default, **change})
        assert names == obs.expected_span_names(default) - gone, change


# ---------------------------------------------------------------------------
# Pipeline integration + REPRO_OBS=off parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_mesh():
    return pebble_mesh(6, 6, 6, n_pebbles=2, seed=3)


def test_pipeline_records_trace_and_manifest(small_mesh, tmp_path):
    pipe = PartitionPipeline(pre="rcb", bisect="rsb-batched",
                             post=("repair", "refine"))
    ctx = pipe.run(small_mesh, 4)
    root = ctx.trace
    assert root is not None and root.name == "partition"
    for name in obs.expected_span_names(ctx.config):
        assert root.find(name) is not None, name
    # stage spans and StageRecords agree on the wall clock
    for rec in ctx.stages:
        span = root.find(f"{rec.kind}:{rec.name}")
        assert span is not None
        assert abs(span.seconds - rec.seconds) < 0.05
    path = ctx.export_manifest(str(tmp_path / "m.jsonl"))
    assert obs.validate_manifest(path) == []
    tpath = ctx.export_trace_events(str(tmp_path / "t.json"))
    events = json.load(open(tpath))["traceEvents"]
    assert {e["name"] for e in events} >= {"partition", "solve", "split"}


def test_repro_obs_off_parity(small_mesh):
    pipe = PartitionPipeline(pre="rcb", bisect="rsb-batched",
                             post=("repair", "refine"))
    ctx_on = pipe.run(small_mesh, 4)
    with obs.disabled():
        ctx_off = pipe.run(small_mesh, 4)
    # identical labels, no trace, but every report timing still populated
    assert np.array_equal(ctx_on.parts, ctx_off.parts)
    assert ctx_off.trace is None
    assert ctx_off.report.seconds > 0
    assert ctx_off.report.post.seconds > 0
    assert all(lv.solve_seconds > 0 for lv in ctx_off.report.levels)
    assert all(s.seconds >= 0 for s in ctx_off.stages)
    assert ctx_off.stats().keys() == ctx_on.stats().keys()


def test_repro_obs_dir_auto_manifest(small_mesh, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_OBS_DIR", str(tmp_path))
    PartitionPipeline(bisect="rcb", post=()).run(small_mesh, 4)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert len(files) == 1
    assert obs.validate_manifest(str(tmp_path / files[0])) == []


def test_recursive_engine_split_seconds(small_mesh):
    # satellite fix: the recursive path used to hardcode split_seconds=0
    pipe = PartitionPipeline(pre="rcb", bisect="rsb-recursive", post=())
    ctx = pipe.run(small_mesh, 4)
    assert all(lv.split_seconds > 0 for lv in ctx.report.levels)
    assert all(r.split_seconds > 0 for r in ctx.report.records)


def test_halo_plan_emits_wire_volume(small_mesh):
    graph = dual_graph(small_mesh)
    parts = np.arange(graph.n) % 4
    with obs.trace("root") as root:
        plan = plan_halo_sharding(graph, parts, 4)
    assert root.counters["halo_words"] == plan.collective_words_per_feature
    assert root.counters["halo_bytes"] == 4.0 * plan.collective_words_per_feature
    assert root.gauges["halo_max_degree"] == plan.halo


def test_report_to_dict_round_trip(small_mesh):
    ctx = PartitionPipeline(pre="rcb", bisect="rsb-batched").run(small_mesh, 4)
    d = ctx.report.to_dict()
    json.dumps(d)                  # fully JSON-able
    assert d["total_iterations"] == ctx.report.total_iterations
    assert d["precond_levels"] == ctx.report.precond_levels
    assert d["post"]["cut_after"] <= d["post"]["cut_before"]
    assert len(d["levels"]) == len(ctx.report.levels)


def test_percentiles_nearest_rank():
    secs = [float(i) for i in range(101)]
    p = obs.percentiles(secs)
    assert p["p50"] == 50.0
    assert p["p99"] == 99.0
    assert obs.percentiles([]) == {"p50": 0.0, "p99": 0.0}


# ---------------------------------------------------------------------------
# Host steps of the default pipeline, and the profiler annotations
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def box_call():
    """The default pipeline, guard on, on a 512-element cube in 8 parts:
    levels 0 and 1 solve packed Lanczos, level 2's 128-element nodes are
    at the dense cutoff."""
    from repro.configs.parrsb import make_pipeline

    mesh = box_mesh(8, 8, 8)
    return mesh, make_pipeline("default", guard=True).run(mesh, 8)


def _children(span) -> list:
    return [c.name for c in span.children]


def test_default_call_has_a_span_at_every_host_step(box_call):
    _, ctx = box_call
    root = ctx.trace
    assert _children(root.find("guard:validate")) == [
        "validate", "dual_graph", "components"]
    levels = [s for s in root.walk() if s.name.startswith("level:")]
    assert [s.name for s in levels] == ["level:0", "level:1", "level:2"]
    for lv in levels:
        assert _children(lv) == ["reorder", "sub", "split_components",
                                 "solve", "split"]
    for lv in levels[:2]:
        assert _children(lv.find("solve")) == ["warm_start", "pack",
                                               "restarts"]
    assert _children(levels[2].find("solve")) == ["warm_start"]
    for s in root.walk():
        for c in s.children:
            assert s.t0 <= c.t0 <= c.t1 <= s.t1, (s.name, c.name)
            assert c.seconds <= s.seconds
    assert [lv.window for lv in ctx.report.levels] == [20, 20, 0]
    # A finished tree holds no profiler annotation: it pickles.
    copy = pickle.loads(pickle.dumps(root))
    assert [s.name for s in copy.walk()] == [s.name for s in root.walk()]


def test_default_call_counts_components_repair_and_ell_slots(box_call):
    """Each level's ``split_components`` span counts the nodes split by
    components (none on a cube), each ``repair`` span the weight it moved,
    and each ``pack`` span the padded ELL slots, stored entries and
    overflow rows over its restart launches."""
    mesh, ctx = box_call
    root = ctx.trace
    for lv in (s for s in root.walk() if s.name.startswith("level:")):
        assert lv.find("split_components").counters == {
            "comp_split_nodes": 0.0}
    repairs = root.find_all("repair")
    assert len(repairs) == 2            # the repair stage, refine's close
    assert all(r.counters["repair_moved"] == 0.0 for r in repairs)
    packs = root.find_all("pack")
    assert len(packs) == 2              # levels 0 and 1
    launches = [s.counters["restart_launches"]
                for s in root.find_all("restarts")]
    for pack, n in zip(packs, launches):
        slots, nnz = pack.counters["ell_slots"], pack.counters["ell_nnz"]
        # 512 packed rows, ELL width 32 (a hex element's 26 neighbours)
        assert slots == 512 * 32 * n
        assert 0 < nnz < slots and nnz % n == 0
        assert pack.counters["ell_overflow_rows"] == 0.0


def test_a_hub_graph_call_counts_the_split_ell_slots():
    """The default pipeline on an R-MAT graph with hubs (472 nodes, mean
    degree 28.1, largest 314) in 8 parts: each ``pack`` span counts the
    slots of the 512-row head and the overflow rows, (N + R)·W per launch,
    and the top level spills every hub row past the head."""
    from repro.configs.parrsb import make_pipeline
    from repro.core.fiedler import ell_layout
    from repro.core.laplacian import spill_rows
    from repro.mesh.graphs import connected_labels, rmat_graph

    g = rmat_graph(512, 12933, seed=4)
    lab = connected_labels(g.n, g.rows, g.indices)
    g = g.sub(np.flatnonzero(lab == np.argmax(np.bincount(lab))))
    W, R = ell_layout(g.degrees, 512)
    assert (g.n, W, R) == (472, 32, 256)
    root = make_pipeline("default", guard=True).run(g, 8).trace
    packs = root.find_all("pack")
    launches = [s.counters["restart_launches"]
                for s in root.find_all("restarts")]
    assert len(packs) == len(launches) >= 2
    for pack, n in zip(packs, launches):
        assert pack.counters["ell_slots"] == (512 + R) * W * n
        assert 0 < pack.counters["ell_overflow_rows"] <= R * n
    top = packs[0].counters["ell_overflow_rows"]
    assert top == spill_rows(g.degrees, W).sum() * launches[0]


def test_restart_launches_count_the_restart_program(box_call, monkeypatch):
    from repro.configs.parrsb import make_pipeline
    from repro.core import lanczos

    launches = []
    real = lanczos._packed_restart

    def counting(*args, **kw):
        launches.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(lanczos, "_packed_restart", counting)
    mesh, _ = box_call
    ctx = make_pipeline("default", guard=True).run(mesh, 8)
    per_level = [s.counters["restart_launches"]
                 for s in ctx.trace.find_all("restarts")]
    assert len(per_level) == 2
    assert ctx.trace.total_counters()["restart_launches"] == \
        sum(per_level) == len(launches)
    # Every subproblem rides each launch: a level launches as often as
    # its slowest subproblem restarts.
    for level, n in enumerate(per_level):
        assert n == max(r.iterations for r in ctx.report.records
                        if r.level == level)


def test_reorder_passes_are_ceil_log2_of_the_largest_node(box_call):
    from repro.analysis import analyze_paths

    assert obs.lookup("reorder_passes").kind == "counter"
    _, ctx = box_call
    reorders = [s.find("reorder") for s in ctx.trace.walk()
                if s.name.startswith("level:")]
    assert len(reorders) == 3
    for level, span in enumerate(reorders):
        largest = max(r.size for r in ctx.report.records if r.level == level)
        assert span.counters["reorder_passes"] == np.ceil(np.log2(largest))
    assert ctx.trace.total_counters()["reorder_passes"] == 9 + 8 + 7
    assert "reorder" in obs.expected_span_names(ctx.config)
    src = os.path.dirname(os.path.dirname(os.path.abspath(obs.__file__)))
    assert [f for f in analyze_paths([src]) if f.rule.startswith("OBS")] == []


def test_default_call_labels_do_not_depend_on_obs(box_call):
    from repro.configs.parrsb import make_pipeline

    mesh, ctx_on = box_call
    with obs.disabled():
        ctx_off = make_pipeline("default", guard=True).run(mesh, 8)
    assert ctx_off.trace is None
    assert np.array_equal(ctx_on.parts, ctx_off.parts)
    assert np.array_equal(ctx_on.parts_raw, ctx_off.parts_raw)


def test_a_profiler_capture_holds_the_span_tree(box_call, tmp_path):
    import jax

    from repro.configs.parrsb import make_pipeline

    mesh, _ = box_call
    jax.profiler.start_trace(str(tmp_path))
    try:
        ctx = make_pipeline("default", guard=True).run(mesh, 8)
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    data = jax.profiler.ProfileData.from_file(path)
    found: dict = {}
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("partition", "solve", "restarts"):
                    found.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    for name in ("partition", "solve", "restarts"):
        assert len(found[name]) == len(ctx.trace.find_all(name)), name

    def inside(child, parents):
        return sum(s <= child[0] and child[1] <= e for s, e in parents) == 1

    assert all(inside(r, found["solve"]) for r in found["restarts"])
    assert all(inside(s, found["partition"]) for s in found["solve"])


def test_a_span_that_raises_closes_its_annotation(monkeypatch):
    import importlib

    obs_trace = importlib.import_module("repro.obs.trace")
    log = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name, exc[0]))
            return False

    monkeypatch.setattr(obs_trace, "TraceAnnotation", Recorder)
    with pytest.raises(ValueError):
        with obs.trace("root"):
            with obs.timed("inner"):
                raise ValueError("boom")
    assert log == [("enter", "root"), ("enter", "inner"),
                   ("exit", "inner", ValueError), ("exit", "root", ValueError)]
    # With obs off no span, and so no annotation, is made.
    log.clear()
    with obs.disabled():
        with obs.trace("root"), obs.timed("inner"), obs.span("s"):
            pass
    assert log == []
