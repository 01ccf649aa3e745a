"""The harness: every file ``BENCHMARK.json`` names loads, new cells come
as files and entries, a run without a chip prints no result, and a run
whose timed path is broken underneath comes out not correct."""

import copy
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import pb_control
import pb_harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _bench():
    return pb_harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_every_named_file_loads():
    bench = _bench()
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.isfile(os.path.join(ROOT, bench["command"][1]))
    for c in bench["configs"]:
        cfg = pb_harness.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert ("mesh" in cfg) != ("graph" in cfg)
        if "mesh" in cfg:
            assert set(cfg["checks"]) == {"balance_tol", "cut_vs_rcb",
                                          "lam2_rel_err", "lam2_deep_rel_err"}
        else:
            import pb_graph

            g = pb_graph.build(cfg["graph"])
            assert set(cfg["checks"]) == {
                "balance_tol", "cut_vs_ref", "lam2_rel_err",
                "lam2_deep_rel_err"} | (
                    {"cut_vs_rcb"} if g.coords is not None else set())
    for w in bench["workloads"]:
        _, cell, cfg, traffic = pb_harness.resolve_cell(ROOT, w["name"])
        assert cell == w and traffic["nparts"] >= 2
        import pb_graph
        import pb_mesh

        kinds = pb_mesh if "mesh" in cfg else pb_graph
        spec = cfg["mesh"] if "mesh" in cfg else cfg["graph"]
        assert callable(kinds.load_kind(spec["kind"]).build)
    for m in bench["per_layer"]:
        assert callable(pb_harness.load_reader(m["name"]).read)


def test_new_cells_are_files_and_entries(tmp_path):
    """A configuration, a traffic mix and a metric are added by adding
    files and entries; so are a graph configuration, its graph kind and
    its cell; no file that is there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _bench()
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    (root / "perfbench/configs/box8.json").write_text(json.dumps({
        "name": "box8", "mesh": {"kind": "box", "dims": [8, 8, 8]},
        "reduced": [], "checks": {"balance_tol": 0.05, "cut_vs_rcb": 1.5,
                                  "lam2_rel_err": 0.01,
                                  "lam2_deep_rel_err": 0.01}}))
    (root / "perfbench/traffic/p4.default.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "nparts": 4, "preset": "default"}))
    (root / "perfbench/metrics/calls.py").write_text(
        "def read(run):\n    return float(len(run.calls))\n")
    (root / "perfbench/graphs/ring.py").write_text(
        "import numpy as np\n\n\ndef build(spec):\n"
        "    n = spec['nodes']\n"
        "    return n, np.arange(n), (np.arange(n) + 1) % n, np.ones(n), "
        "None\n")
    (root / "perfbench/configs/ring64.json").write_text(json.dumps({
        "name": "ring64", "graph": {"kind": "ring", "nodes": 64},
        "reduced": [], "checks": {"balance_tol": 0.05, "cut_vs_ref": 1.5,
                                  "lam2_rel_err": 0.01,
                                  "lam2_deep_rel_err": 0.01}}))
    bench["configs"].append({"name": "box8", "source": "test",
                             "file": "perfbench/configs/box8.json",
                             "reduced": [], "why": "test"})
    bench["configs"].append({"name": "ring64", "source": "test",
                             "file": "perfbench/configs/ring64.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "box8.p4.default", "config": "box8",
                               "traffic": "p4.default", "chips": 1,
                               "why": "test"})
    bench["workloads"].append({"name": "ring64.p4.default",
                               "config": "ring64", "traffic": "p4.default",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "calls", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "partition_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    sys.path.insert(0, str(root / "perfbench"))
    try:
        import importlib

        h = importlib.reload(importlib.import_module("pb_harness"))
        importlib.reload(importlib.import_module("pb_graph"))
        _, cell, cfg, traffic = h.resolve_cell(str(root), "box8.p4.default")
        assert cfg["mesh"]["dims"] == [8, 8, 8] and traffic["nparts"] == 4
        _, cell, cfg, traffic = h.resolve_cell(str(root), "ring64.p4.default")
        ring = h.base_input(cfg)
        assert (ring.n, ring.adj.nnz, ring.coords) == (64, 128, None)
        assert traffic["nparts"] == 4
        names = [m["name"] for m in h.cell_metrics(bench, "box8.p4.default",
                                                   trace=True)]
        assert names == ["calls"]
        assert h.load_reader("calls").read(
            pb_harness.Run(calls=[1, 2], nparts=4, graph=None,
                           device_kind="x")) == 2.0
    finally:
        sys.path.remove(str(root / "perfbench"))
        importlib.reload(importlib.import_module("pb_harness"))
        importlib.reload(importlib.import_module("pb_graph"))
    for p, data in before.items():
        assert p.read_bytes() == data


def _run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "perfbench", "run.py"),
         "--workload", "box32.p16.default", "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].lstrip().startswith("{")


def test_without_a_chip_a_run_prints_no_result():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "no accelerator" in proc.stderr


def test_without_the_program_a_run_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run_cli(str(tmp_path))
    assert proc.returncode != 0 and _no_result(proc)


# -- a run with the timed path broken underneath ----------------------------

TINY_BENCH = {
    "workloads": [{"name": "tiny", "config": "tiny", "traffic": "t",
                   "chips": 1}],
    "end_to_end": [{"name": "partition_s", "unit": "s"},
                   {"name": "edge_cut", "unit": "edges"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}
# At 10^3 elements RCB's four slabs are hard to beat: the tiny cell's cut
# bar is wider than the real cells'.  On the CPU sound runs read 1.33 on
# the cut and 1e-6 on the λ₂ numbers; the bfloat16 control 0.013-0.039 on
# the top node and 0.053-0.079 below it; the scrambled segment 1.97-2.19 on
# the cut.
TINY_CONFIG = {"mesh": {"kind": "box", "dims": [10, 10, 10]},
               "checks": {"balance_tol": 0.05, "cut_vs_rcb": 1.5,
                          "lam2_rel_err": 0.005, "lam2_deep_rel_err": 0.005}}
TINY_TRAFFIC = {"nparts": 4, "preset": "default"}


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


class Broken:
    """The default pipeline with its answer altered where it is made."""

    def __init__(self, fault):
        from repro.configs.parrsb import make_pipeline

        self.pipe, self.fault = make_pipeline("default"), fault

    def run(self, obj, nparts, **kw):
        ctx = self.pipe.run(obj, nparts, **kw)
        parts = ctx.parts.copy()
        n = parts.size
        if self.fault == "label":          # one answer altered
            # Element or node 0 goes to a part that none of its neighbours
            # is in.
            if hasattr(obj, "vert_gid"):
                near = np.isin(obj.vert_gid, obj.vert_gid[0]).any(axis=1)
            else:
                near = np.r_[0, obj.indices[obj.indptr[0]:obj.indptr[1]]]
            parts[0] = np.setdiff1d(np.arange(nparts), parts[near])[0]
        elif self.fault == "half":          # half of the batch left out
            parts[n // 2:] = 0
        elif self.fault == "range":
            parts[-1] = nparts
        elif self.fault == "eigenvalue":    # the Fiedler value altered
            ctx.report.records[0].eigenvalue *= 1.05
        elif self.fault == "deep-eigenvalue":   # ... of the last node
            ctx.report.records[-1].eigenvalue *= 1.05
        elif self.fault == "records":       # a node left out of the report
            ctx.report.records.pop()
        ctx.parts = parts
        return ctx


def _run(tmp_path, pipeline=None):
    out = open(os.devnull, "w")
    try:
        return pb_harness.run_cell(
            str(tmp_path), "tiny", 2**31 + 11, 0.3, False,
            t_start=time.perf_counter(), require_chip=False,
            pipeline=pipeline, bench=copy.deepcopy(TINY_BENCH),
            config=TINY_CONFIG, traffic=TINY_TRAFFIC, out=out)
    finally:
        out.close()


def test_a_sound_run_is_correct(tmp_path, restore_cache_config):
    r = _run(tmp_path)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert r["device"]["kind"] == jax.devices()[0].device_kind
    assert set(r["metrics"]) == {"partition_s", "edge_cut", "setup_s"}


@pytest.mark.parametrize("fault", ["label", "half", "range", "eigenvalue",
                                   "deep-eigenvalue", "records"])
def test_a_broken_timed_path_is_not_correct(tmp_path, restore_cache_config,
                                            fault):
    r = _run(tmp_path, Broken(fault))
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1


def test_a_scrambled_segment_fails_the_cut(tmp_path, restore_cache_config):
    """One node's Fiedler vector shuffled at each level below the top."""
    from repro.configs.parrsb import make_pipeline

    r = _run(tmp_path, pb_control.ScrambledSegment(make_pipeline("default"),
                                                   2**31 + 3))
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["checks"]["cut_vs_rcb"]["value"] > \
        r["checks"]["cut_vs_rcb"]["limit"]


def test_the_bfloat16_control_is_not_correct(tmp_path, restore_cache_config):
    """The plain Lanczos in bfloat16 in the program's place, through the
    harness: the λ₂ numbers fail, and the float32 witness passes."""
    from repro.configs.parrsb import make_pipeline

    for dtype, correct in (("bfloat16", False), ("float32", True)):
        r = _run(tmp_path, pb_control.Bfloat16Control(
            make_pipeline("default"), 2**31 + 7, dtype=dtype))
        assert r["correct"] is correct
        deep = r["checks"]["lam2_deep_rel_err"]
        assert (deep["value"] > deep["limit"]) is not correct


# -- the same on a graph input ----------------------------------------------

# A 24 x 10 lattice without coordinates in 4 parts.  On the CPU sound runs
# read 1.0 on the cut against the plain RSB's and 7e-7 / 7e-15 on the λ₂
# numbers; the bfloat16 control 0.21 on the top node and 0.096 below it
# (float32: 2.3e-5 / 3.2e-6); the scrambled segment 0.48 on the balance,
# which the repair stage trades for connected parts.
TINY_GRAPH_CONFIG = {
    "graph": {"kind": "grid", "dims": [24, 10], "coords": False},
    "checks": {"balance_tol": 0.05, "cut_vs_ref": 1.5,
               "lam2_rel_err": 0.005, "lam2_deep_rel_err": 0.005}}


def _run_graph(tmp_path, pipeline=None, coords=False):
    config = copy.deepcopy(TINY_GRAPH_CONFIG)
    if coords:
        config["graph"]["coords"] = True
        config["checks"]["cut_vs_rcb"] = 1.5
    out = open(os.devnull, "w")
    try:
        return pb_harness.run_cell(
            str(tmp_path), "tiny", 2**31 + 11, 0.3, False,
            t_start=time.perf_counter(), require_chip=False,
            pipeline=pipeline, bench=copy.deepcopy(TINY_BENCH),
            config=config, traffic=TINY_TRAFFIC, out=out)
    finally:
        out.close()


@pytest.mark.parametrize("coords", [False, True])
def test_a_sound_graph_run_is_correct(tmp_path, restore_cache_config,
                                      coords):
    r = _run_graph(tmp_path, coords=coords)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {
        "out_of_range", "empty_parts", "disconnected_parts", "balance",
        "cut_vs_ref", "lam2_rel_err", "lam2_deep_rel_err"} | (
            {"cut_vs_rcb"} if coords else set())
    assert set(r["metrics"]) == {"partition_s", "edge_cut", "setup_s"}
    # Every edge of the lattice weighs 1: the cut counts edges.
    assert r["metrics"]["edge_cut"]["value"] == int(
        r["metrics"]["edge_cut"]["value"])


@pytest.mark.parametrize("fault, check", [
    ("label", "disconnected_parts"), ("half", "balance"),
    ("range", "out_of_range"), ("eigenvalue", "lam2_rel_err"),
    ("deep-eigenvalue", "lam2_deep_rel_err"), ("records", "lam2_rel_err")])
def test_a_broken_graph_timed_path_is_not_correct(
        tmp_path, restore_cache_config, fault, check):
    r = _run_graph(tmp_path, Broken(fault))
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]


def test_a_scrambled_segment_fails_the_graph_balance(tmp_path,
                                                     restore_cache_config):
    from repro.configs.parrsb import make_pipeline

    r = _run_graph(tmp_path, pb_control.ScrambledSegment(
        make_pipeline("default"), 2**31 + 3))
    assert not r["correct"] and r["failed"] == r["attempted"] >= 1
    assert r["checks"]["balance"]["value"] > r["checks"]["balance"]["limit"]


def test_the_bfloat16_control_fails_a_graph(tmp_path, restore_cache_config):
    from repro.configs.parrsb import make_pipeline

    for dtype, correct in (("bfloat16", False), ("float32", True)):
        r = _run_graph(tmp_path, pb_control.Bfloat16Control(
            make_pipeline("default"), 2**31 + 7, dtype=dtype))
        assert r["correct"] is correct
        deep = r["checks"]["lam2_deep_rel_err"]
        assert (deep["value"] > deep["limit"]) is not correct


@pytest.mark.parametrize("config, extra", [
    (TINY_GRAPH_CONFIG, "cut_vs_rcb"), (TINY_CONFIG, "cut_vs_ref")])
def test_checks_that_do_not_fit_the_input_are_refused(config, extra):
    config = copy.deepcopy(config)
    config["checks"][extra] = 1.5
    with pytest.raises(pb_harness.SetupError):
        pb_harness.base_input(config)
