"""The benchmark harness: one cell, one seed, one run.

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the input and the checks it states) and a traffic mix
(``traffic/<name>.json``: the part count, the pipeline preset and, under
``pipeline``, keywords that ``make_pipeline`` lays over the preset).  The
input is a hex mesh (a ``mesh`` entry, :mod:`pb_mesh`) or an undirected
graph (a ``graph`` entry, :mod:`pb_graph`).  The run is a closed loop of
partition calls, one at a time, as a spectral-element code makes them:
set-up builds the input and warms up one call; the window then runs calls
back to back, each on the same input in a fresh element or node order
drawn from ``(seed, call)``, until the calls' summed durations reach
``--seconds``.  A call is ``make_pipeline(preset).run(input, nparts)``,
with a graph's node weights and any coordinates, up to the host holding
the labels.

After the window every call's labels are checked by the plain reference
(:mod:`pb_reference`), and the Fiedler eigenvalue that the call reports
for each node of its bisection tree is compared with the float64 λ₂ of
that node's subgraph.  Per-layer metrics are small readers,
``metrics/<name>.py``, found by the names in ``BENCHMARK.json``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class SetupError(RuntimeError):
    """The run cannot start: no accelerator, fewer chips than the cell asks
    for, or an input the configuration cannot have."""


@dataclasses.dataclass
class Call:
    """What one timed call produced, in the base input's order."""

    t0: float                 # perf_counter at the call's start
    t1: float
    labels: np.ndarray
    raw: np.ndarray           # bisection labels before the post stages
    stages: list              # [(kind, name, seconds)]
    levels: list              # [LevelRecord as dict]
    records: list             # [BisectionRecord as dict]
    span: object = None       # the call's obs span tree
    cpu_s: float = 0.0        # the process's CPU time over the call
    gc_s: float = 0.0         # time in Python's garbage collector

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """Everything a per-layer reader may read."""

    calls: list
    nparts: int
    graph: object             # pb_reference.DualGraph of the base input
    device_kind: str
    events: list | None = None    # pb_trace.Event rows (traced run)
    offset_ns: float = 0.0        # trace ns − perf_counter ns

    def windows(self) -> list:
        """Each call's interval in trace nanoseconds."""
        return [[c.t0 * 1e9 + self.offset_ns, c.t1 * 1e9 + self.offset_ns]
                for c in self.calls]


def _load_py(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    return _load_py(path, f"perfbench_metric_{name.replace('.', '_')}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve_cell(root: str, workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic) for a cell name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} (have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    return bench, cell, config, traffic


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in this mode."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *stream])


def base_mesh(config: dict):
    import pb_mesh

    vert, coords, weights = pb_mesh.load_kind(
        config["mesh"]["kind"]).build(config["mesh"])
    return pb_mesh.hex_mesh(vert, coords, weights)


MESH_CHECKS = ("balance_tol", "cut_vs_rcb", "lam2_rel_err",
               "lam2_deep_rel_err")


def check_names(config: dict, has_coords: bool) -> set:
    """The limits a configuration's ``checks`` hold: a mesh's are
    :data:`MESH_CHECKS`; a graph's compare its cut with that of the plain
    RSB (``cut_vs_ref``), and also with that of plain RCB where the graph
    has coordinates."""
    if "mesh" in config:
        return set(MESH_CHECKS)
    names = {"balance_tol", "cut_vs_ref", "lam2_rel_err", "lam2_deep_rel_err"}
    return names | {"cut_vs_rcb"} if has_coords else names


def base_input(config: dict):
    """The configuration's input in its base order: a
    ``pb_mesh.MeshInput`` or a ``pb_graph.GraphInput``."""
    if "graph" in config:
        import pb_graph

        inp = pb_graph.build(config["graph"])
    else:
        import pb_mesh

        inp = pb_mesh.MeshInput(base_mesh(config))
    want = check_names(config, inp.coords is not None)
    if set(config["checks"]) != want:
        raise SetupError(f"the configuration's checks are "
                         f"{sorted(config['checks'])}; its input needs "
                         f"{sorted(want)}")
    return inp


def check_device(chips: int):
    """The devices to report; raises where no accelerator or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise SetupError("JAX found no accelerator")
    if len(devices) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices


def use_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    kept for every program, however short its compile."""
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts programs lowered (compiled or loaded from the cache)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.count += 1


class GcClock:
    """Seconds spent in Python's garbage collector since it was made."""

    def __init__(self):
        self.seconds, self._t0 = 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None


GC = GcClock()


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def timed_call(pipe, args: tuple, nparts: int, perm: np.ndarray) -> Call:
    """One call on ``args``, the ``(input, keywords)`` that the base
    input's ``call(perm)`` prepared."""
    obj, kw = args
    gc0, cpu0 = GC.seconds, time.process_time()
    t0 = time.perf_counter()
    ctx = pipe.run(obj, nparts, **kw)
    parts = np.asarray(ctx.parts)
    t1 = time.perf_counter()
    cpu_s, gc_s = time.process_time() - cpu0, GC.seconds - gc0
    labels = np.empty_like(parts)
    labels[perm] = parts
    raw = np.empty_like(parts)
    raw[perm] = np.asarray(ctx.parts_raw)
    rep = ctx.report
    return Call(
        t0=t0, t1=t1, labels=labels, raw=raw,
        stages=[(s.kind, s.name, s.seconds) for s in ctx.stages],
        levels=[lv.to_dict() for lv in rep.levels],
        records=[r.to_dict() for r in rep.records], span=ctx.trace,
        cpu_s=cpu_s, gc_s=gc_s)


def call_line(i: int, c: Call) -> str:
    """``call i: seconds``, the process's CPU and garbage-collector seconds
    over the call, and the seconds of each kind of stage."""
    per: dict = {}
    for stage_kind, _, sec in c.stages:
        per[stage_kind] = per.get(stage_kind, 0.0) + sec
    return (f"call {i}: {c.seconds:.6f} s cpu {c.cpu_s:.3f} gc {c.gc_s:.3f} "
            + " ".join(f"{k} {v:.3f}" for k, v in per.items()))


def lambda2_errors(c: Call, lam2, nparts: int) -> tuple[float, float]:
    """The relative gap between the eigenvalue the call reports for each
    node of its bisection tree and the float64 λ₂ of that node's subgraph:
    ``(top node, worst node below the top)``.  A report that does not
    describe the tree of the call's own raw labels reads ``inf``."""
    import pb_reference as ref

    nodes = ref.tree_nodes(c.raw, nparts)
    if len(nodes) != len(c.records) or any(
            (r["level"], r["size"], r["nparts"]) != (lv, idx.size, p)
            for (lv, p, idx), r in zip(nodes, c.records)):
        return float("inf"), float("inf")
    errs = [lam2.rel_err(r["eigenvalue"], idx)
            for (_, _, idx), r in zip(nodes, c.records)]
    return errs[0], max(errs[1:], default=0.0)


def check_calls(calls: list, inp, nparts: int, config: dict) -> tuple:
    """Every call's checks against the plain reference: ``(checks, failed
    calls, reference graph, info)``, where ``checks`` maps each number
    compared to ``[worst over the calls, limit]``, and ``info`` holds the
    reference cuts (``ref_cut``, the plain RSB's, for a graph input;
    ``rcb_cut`` where the input has coordinates) and ``ref_s``, the plain
    RSB's seconds."""
    import pb_reference as ref

    lim = config["checks"]
    limits = {"out_of_range": 0, "empty_parts": 0, "disconnected_parts": 0,
              "balance": lim["balance_tol"]}
    limits.update((k, lim[k]) for k in ("cut_vs_ref", "cut_vs_rcb")
                  if k in lim)
    limits.update(lam2_rel_err=lim["lam2_rel_err"],
                  lam2_deep_rel_err=lim["lam2_deep_rel_err"])
    g = inp.reference_graph()
    w = inp.weights
    info = {}
    if "cut_vs_ref" in limits:
        t0 = time.perf_counter()
        info["ref_cut"] = ref.edge_cut(g, ref.rsb_labels(g, w, nparts))
        info["ref_s"] = time.perf_counter() - t0
        log(f"plain RSB reference {info['ref_s']:.3f} s")
    if "cut_vs_rcb" in limits:
        info["rcb_cut"] = ref.edge_cut(
            g, ref.rcb_labels(inp.coords, w, nparts))
    lam2 = ref.NodeLambda2(g, None if "mesh" in config
                           else lambda sub: ref.fiedler_pair(sub)[0])
    rows = []
    for c in calls:
        r = ref.check_partition(g, c.labels, w, nparts, lim["balance_tol"])
        if "ref_cut" in info:
            r["cut_vs_ref"] = r["cut"] / info["ref_cut"]
        if "rcb_cut" in info:
            r["cut_vs_rcb"] = r["cut"] / info["rcb_cut"]
        r["lam2_rel_err"], r["lam2_deep_rel_err"] = lambda2_errors(
            c, lam2, nparts)
        rows.append(r)
    failed = sum(not all(r[k] <= v for k, v in limits.items()) for r in rows)
    checks = {k: [max(r[k] for r in rows), v] for k, v in limits.items()}
    info.update(lambda2=lam2.top(), cuts=[r["cut"] for r in rows],
                rows=[{k: r[k] for k in limits} for r in rows],
                residual=[c.records[0]["residual"] for c in calls])
    return checks, failed, g, info


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_start: float, require_chip: bool = True,
             pipeline=None, bench=None, config=None, traffic=None,
             out=sys.stdout) -> dict:
    """One run; prints per-call lines and returns the result object.

    ``bench``/``config``/``traffic`` and ``pipeline`` replace what the
    cell names (tests drive a small input and a broken pipeline through
    the rest of a run); ``require_chip=False`` skips the look for a chip.
    """
    if bench is None:
        bench, cell, config, traffic = resolve_cell(root, workload)
    else:
        cell = {w["name"]: w for w in bench["workloads"]}[workload]
    import jax

    cache = use_cache(root)
    devices = check_device(cell["chips"]) if require_chip else jax.devices()
    kind = devices[0].device_kind
    from repro.configs.parrsb import make_pipeline

    compiles = CompileCounter()
    nparts = int(traffic["nparts"])
    pipe = pipeline or make_pipeline(traffic["preset"],
                                     **traffic.get("pipeline", {}))
    inp = base_input(config)
    n = inp.n
    log(f"{workload}: {n} {inp.noun} into {nparts} parts, preset "
        f"{traffic['preset']}, {len(devices)} x {kind}, cache {cache}")

    perm = seed_rng(seed, 0).permutation(n)
    warm = timed_call(pipe, inp.call(perm), nparts, perm)
    log(f"warm-up call {warm.seconds:.3f} s, {compiles.count} programs "
        "lowered")
    perm = seed_rng(seed, 1).permutation(n)
    nxt = inp.call(perm)
    setup_s = time.perf_counter() - t_start
    lowered_before = compiles.count

    capture = contextlib.nullcontext()
    if trace:
        import pb_trace

        tdir = os.path.join(root, ".perfbench_trace", workload)
        shutil.rmtree(tdir, ignore_errors=True)
        capture = pb_trace.Capture(tdir)
    calls, window = [], 0.0
    with capture:
        while window < seconds:
            c = timed_call(pipe, nxt, nparts, perm)
            calls.append(c)
            window += c.seconds
            print(call_line(len(calls) - 1, c), file=out, flush=True)
            if window < seconds:
                perm = seed_rng(seed, len(calls) + 1).permutation(n)
                nxt = inp.call(perm)
    lowered = compiles.count - lowered_before
    log(f"window {window:.3f} s, {len(calls)} calls, {lowered} programs "
        "lowered inside the window")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]])
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    checks, failed, graph, info = check_calls(calls, inp, nparts, config)
    log(f"lambda2 reference {info['lambda2']!r}, "
        + ", ".join(f"plain {name} cut {info[k]!r}" for k, name in
                    (("ref_cut", "RSB"), ("rcb_cut", "RCB")) if k in info))
    run = Run(calls=calls, nparts=nparts, graph=graph, device_kind=kind)
    result = {"correct": False, "attempted": len(calls), "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        import pb_trace

        run.events = pb_trace.load_events(capture.out_dir)
        shutil.rmtree(capture.out_dir, ignore_errors=True)
        run.offset_ns = pb_trace.clock_offset(run.events, capture.mark_pc_ns)
        wins = run.windows()
        device["busy_s"] = pb_trace.busy_ns(run.events, wins) / 1e9
        device["window_s"] = sum(c.seconds for c in calls)
        spans = [row for c in calls if c.span is not None
                 for row in pb_trace.span_rows(c.span, run.offset_ns)]
        result["breakdown"] = {
            "device_ops": pb_trace.top_ops(run.events, wins),
            "idle_gaps": pb_trace.labelled_gaps(run.events, wins, spans)}
        for m in cell_metrics(bench, workload, trace=True):
            v = load_reader(m["name"]).read(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"partition_s": window / len(calls),
                  "edge_cut": float(np.mean(info["cuts"])),
                  "setup_s": setup_s}
        for m in cell_metrics(bench, workload, trace=False):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    rows = info["rows"]
    print(json.dumps({"calls": [c.seconds for c in calls],
                      "cuts": info["cuts"],
                      "lam2_rel_err": [r["lam2_rel_err"] for r in rows],
                      "lam2_deep_rel_err": [r["lam2_deep_rel_err"]
                                            for r in rows],
                      "top_residual": info["residual"],
                      "lambda2": info["lambda2"],
                      **{k: info[k] for k in ("ref_cut", "rcb_cut", "ref_s")
                         if k in info},
                      "setup_s": setup_s,
                      "lowered_in_window": lowered}), file=out, flush=True)
    ok = all(v <= lim for v, lim in checks.values())
    result["correct"] = bool(ok and failed == 0)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    return result
