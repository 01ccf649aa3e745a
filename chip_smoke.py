"""Chip smoke test: the default partition pipeline, end to end, on a TPU.

    python chip_smoke.py              # one chip: main phase + kernel phase
    python chip_smoke.py --chips 4    # four chips: sharded refinement only

Main phase: ``box_mesh(64, 64, 64)`` (262,144 hex elements, dual-graph
degree ≤ 26, ELL width 32) into 128 parts — 2,048 elements per part, the
paper's 1.1k–2.7k regime — through the ``default`` preset (RCB per-level
reorder, batched RSB, repair + FM refine, guard on), cold then warm.
Every part must be non-empty, connected and inside the ±``balance_tol``
corridor; the guard must record no retry, fallback or degradation; the
cut must be within 10% of the ``geometric`` preset's (RCB + repair +
refine, no eigensolves) on the same mesh.

Kernel phase: the sharded refinement stage (``repair`` →
``refine-sharded``) re-refines the main phase's raw bisection, which runs
the compiled ``segment_sum`` Pallas kernel; it must not fall back to the
host, must make one all_gather per sweep, and its device sweeps must
match the NumPy host mirror label for label.  Then one
``partition(..., use_kernel=True)`` at 32³ / 32 parts runs the compiled
``ell_spmv`` kernel, whose cut must be within 2% of the XLA path's.

``--chips 4``: only ``refine_sharded`` across the four chips of one host,
against the host mirror (label parity, one all_gather per sweep, a mesh
over all four devices, per-device memory).

There is no CPU branch: without a TPU the script exits 1 before any work.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every check passed; otherwise the exit code is 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

SIDE, NPARTS = 64, 128          # main phase: 2,048 elements per part
KERNEL_SIDE, KERNEL_NPARTS = 32, 32
BALANCE_TOL = 0.05
GEOMETRIC_CUT_BAND = 1.10       # RSB cut ≤ 110% of the geometric preset's
KERNEL_CUT_BAND = 0.02          # use_kernel cut within 2% of the XLA path's
SWEEPS = 8


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Collects failed assertions so one run reports all of them."""

    def __init__(self):
        self.failed: list = []

    def __call__(self, ok: bool, what: str) -> None:
        log(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            self.failed.append(what)


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"{dev}: peak {stats.get('peak_bytes_in_use', 'n/a')} B, "
            f"in use {stats.get('bytes_in_use', 'n/a')} B")


def check_partition(check: Checks, ctx, graph, nparts: int, tag: str):
    """Quality bar of one pipeline run; returns its edge cut."""
    import numpy as np

    from repro.core import partition_metrics

    parts = np.asarray(ctx.parts)
    w = np.asarray(ctx.weights, np.float64)
    pm = partition_metrics(graph, parts, nparts, weights=w)
    pw = np.bincount(parts, weights=w, minlength=nparts)
    mean = float(w.sum()) / nparts
    lo, hi = (1 - BALANCE_TOL) * mean, (1 + BALANCE_TOL) * mean
    check(int((np.bincount(parts, minlength=nparts) > 0).sum()) == nparts,
          f"{tag}: all {nparts} parts non-empty")
    check(pm.disconnected_parts == 0,
          f"{tag}: {pm.disconnected_parts} disconnected parts")
    check(bool((pw >= lo * (1 - 1e-9)).all() and (pw <= hi * (1 + 1e-9)).all()),
          f"{tag}: part weights in [{pw.min():.0f}, {pw.max():.0f}] "
          f"inside corridor [{lo:.1f}, {hi:.1f}]")
    return float(pm.edge_cut)


def check_guard(check: Checks, ctx, tag: str) -> None:
    g = ctx.report.guard
    check(g is not None, f"{tag}: guard report present")
    if g is None:
        return
    counters = ctx.trace.total_counters() if ctx.trace is not None else {}
    check(g.retries == 0 and g.fallbacks == 0 and not g.degraded
          and not g.deadline_expired,
          f"{tag}: guard retries={g.retries} fallbacks={g.fallbacks} "
          f"degraded={g.degraded}")
    check(counters.get("guard_fallbacks", 0) == 0
          and counters.get("guard_retries", 0) == 0,
          f"{tag}: guard_fallbacks counter "
          f"{counters.get('guard_fallbacks', 0):.0f}")
    bad = [r.method for r in ctx.report.records
           if r.method.startswith("fallback-")]
    check(not bad, f"{tag}: no fallback bisections ({len(bad)} found)")


def main_phase(check: Checks, side: int, nparts: int):
    """Default pipeline cold + warm, then the geometric preset."""
    import jax
    import numpy as np

    from repro.configs.parrsb import make_pipeline
    from repro.mesh import box_mesh

    t0 = time.perf_counter()
    mesh = box_mesh(side, side, side)
    log(f"mesh: box_mesh({side}, {side}, {side}) = {mesh.nelems} elements "
        f"into {nparts} parts ({mesh.nelems // nparts} per part), "
        f"built in {time.perf_counter() - t0:.2f} s")
    pipe = make_pipeline("default", guard=True)
    runs = {}
    for tag in ("cold", "warm"):
        t0 = time.perf_counter()
        ctx = pipe.run(mesh, nparts)
        parts = np.asarray(ctx.parts)        # host labels: the run is done
        runs[tag] = (time.perf_counter() - t0, ctx, parts)
        log(f"default pipeline {tag}: {runs[tag][0]:.3f} s wall")
    _, ctx, parts = runs["cold"]
    graph = ctx.require_graph()
    log(f"dual graph: n={graph.n} nnz={graph.nnz} "
        f"max degree={int(graph.degrees.max())}")
    for lv in ctx.report.levels:
        log(f"  level {lv.level}: {lv.n_nodes} solves, "
            f"solve {lv.solve_seconds:.3f} s, split {lv.split_seconds:.3f} s, "
            f"{lv.iterations} iterations, buckets {lv.buckets}")
    for s in runs["warm"][1].stages:
        log(f"  warm stage {s.kind}:{s.name} {s.seconds:.3f} s")
    cut = check_partition(check, ctx, graph, nparts, "default")
    check_guard(check, ctx, "default")
    check(bool(np.array_equal(parts, runs["warm"][2])),
          "default: warm run reproduces the cold run's labels")

    t0 = time.perf_counter()
    gctx = make_pipeline("geometric", guard=True).run(mesh, nparts)
    log(f"geometric preset: {time.perf_counter() - t0:.3f} s wall")
    gcut = check_partition(check, gctx, graph, nparts, "geometric")
    log(f"cut: default (RSB) {cut:.0f}, geometric (RCB) {gcut:.0f}, "
        f"ratio {cut / gcut:.4f}")
    check(cut <= GEOMETRIC_CUT_BAND * gcut,
          f"default cut within {GEOMETRIC_CUT_BAND - 1:.0%} of geometric")
    log(peak_bytes(jax.devices()[0]))
    return mesh, ctx


def sharded_parity(check: Checks, graph, parts, nparts: int, weights,
                   devices: int) -> None:
    """Device sweeps vs the NumPy host mirror on one FrontierPlan, and the
    one-all_gather-per-sweep contract, across ``devices`` devices."""
    import jax
    import numpy as np

    from repro import obs
    from repro.core.refine import balance_corridor
    from repro.dist.refine_sharded import (
        _device_step,
        _pick_devices,
        build_frontier_plan,
        refine_sharded_host,
        run_sharded_sweeps,
    )

    corridor = balance_corridor(parts, nparts, weights, BALANCE_TOL)
    t0 = time.perf_counter()
    fp = build_frontier_plan(graph, parts, nparts, weights=weights)
    log(f"frontier plan: {fp.plan.n_shards} shards, halo {fp.plan.halo}, "
        f"n_local {fp.plan.n_local}, w {fp.w}, "
        f"built in {time.perf_counter() - t0:.3f} s")
    n_dev = _pick_devices(fp.plan.n_shards)
    check(n_dev == devices, f"sweeps span {n_dev} of {devices} devices")
    with obs.trace("sharded-parity") as root:
        t0 = time.perf_counter()
        out_d, rec_d, info_d = run_sharded_sweeps(
            fp, parts, nparts, sweeps=SWEEPS, corridor=corridor,
            backend="device")
        dt = time.perf_counter() - t0
    counters = root.total_counters()
    log("sweep seconds (sweep 0 compiles): " + ", ".join(
        f"{sp.seconds:.3f}" for sp in root.walk()
        if sp.name.startswith("sweep:")))
    log(f"device sweeps: {dt:.3f} s (compile included), "
        f"{info_d['moves']} moves, {info_d['gathers']} gathers")
    check(info_d["moves"] > 0, "the sweeps moved nodes")
    mesh = _device_step(fp, nparts, float(corridor[0]), float(corridor[1]),
                        n_dev)[2]
    check(sorted(d.id for d in mesh.devices.flat)
          == sorted(d.id for d in jax.devices()[:devices]),
          f"shard_map mesh spans devices {[d.id for d in mesh.devices.flat]}")
    t0 = time.perf_counter()
    out_h, rec_h, info_h = refine_sharded_host(fp, parts, nparts,
                                               sweeps=SWEEPS,
                                               corridor=corridor)
    log(f"host mirror: {time.perf_counter() - t0:.3f} s, "
        f"{info_h['moves']} moves")
    check(bool(np.array_equal(np.asarray(out_d), np.asarray(out_h))),
          "device sweeps match the host mirror label for label")
    check([r.moves for r in rec_d] == [r.moves for r in rec_h],
          f"per-sweep moves match ({[r.moves for r in rec_d]})")
    check(counters.get("sharded_gathers", -1)
          == counters.get("sharded_sweeps", -2) > 0,
          f"one all_gather per sweep: gathers "
          f"{counters.get('sharded_gathers', 0):.0f} == sweeps "
          f"{counters.get('sharded_sweeps', 0):.0f}")
    for dev in jax.devices()[:devices]:
        log(peak_bytes(dev))


def kernel_phase(check: Checks, mesh, ctx, nparts: int,
                 kernel_side: int, kernel_nparts: int) -> None:
    import numpy as np

    from repro import obs
    from repro.core import partition, partition_metrics, run_post_stages
    from repro.mesh import box_mesh, dual_graph

    graph = ctx.require_graph()
    with obs.trace("refine-sharded") as root:
        t0 = time.perf_counter()
        parts, agg, records = run_post_stages(
            graph, ctx.parts_raw, nparts, ("repair", "refine-sharded"),
            weights=ctx.weights, post_kw={"sweeps": SWEEPS})
        dt = time.perf_counter() - t0
    counters = root.total_counters()
    path = records[-1].info["stages"]
    log(f"refine-sharded stage: {dt:.3f} s (compile included), path {path}, "
        f"cut {agg.cut_before:.0f} -> {agg.cut_after:.0f}")
    check("host-fallback" not in path, "refine-sharded ran on the device")
    check(counters.get("guard_fallbacks", 0) == 0,
          "refine-sharded: guard_fallbacks counter 0")
    check(counters.get("sharded_gathers", -1)
          == counters.get("sharded_sweeps", -2) > 0,
          f"refine-sharded: gathers {counters.get('sharded_gathers', 0):.0f}"
          f" == sweeps {counters.get('sharded_sweeps', 0):.0f}")
    pm = partition_metrics(graph, parts, nparts, weights=ctx.weights)
    check(pm.disconnected_parts == 0,
          f"refine-sharded: {pm.disconnected_parts} disconnected parts")

    repaired, _, _ = run_post_stages(graph, ctx.parts_raw, nparts,
                                     ("repair",), weights=ctx.weights)
    sharded_parity(check, graph, repaired, nparts,
                   np.asarray(ctx.weights, np.float64), devices=1)

    kmesh = box_mesh(kernel_side, kernel_side, kernel_side)
    kgraph = dual_graph(kmesh)
    cuts = {}
    for use_kernel in (True, False):
        t0 = time.perf_counter()
        kparts = partition(kgraph, kernel_nparts, coords=kmesh.coords,
                           weights=kmesh.weights, use_kernel=use_kernel)
        cuts[use_kernel] = partition_metrics(
            kgraph, kparts, kernel_nparts, weights=kmesh.weights).edge_cut
        log(f"partition(box_mesh({kernel_side}^3), {kernel_nparts}, "
            f"use_kernel={use_kernel}): {time.perf_counter() - t0:.3f} s "
            f"(compile included), cut {cuts[use_kernel]:.0f}")
    rel = abs(cuts[True] - cuts[False]) / cuts[False]
    check(rel <= KERNEL_CUT_BAND,
          f"use_kernel cut within {KERNEL_CUT_BAND:.0%} of XLA path "
          f"({rel:.4%})")


def four_chip_phase(check: Checks, side: int, nparts: int) -> None:
    """refine_sharded over 4 devices, against the host mirror, from RCB
    labels whose boundaries were roughened (each cut edge hands its source
    node to the other side with probability 0.1) so the sweeps have work."""
    import numpy as np

    from repro.core import run_post_stages
    from repro.core.rcb import rcb_parts
    from repro.mesh import box_mesh, dual_graph

    mesh = box_mesh(side, side, side)
    graph = dual_graph(mesh)
    parts = rcb_parts(mesh.coords, nparts, mesh.weights)
    cross = np.flatnonzero(parts[graph.rows] != parts[graph.indices])
    pick = cross[np.random.default_rng(0).random(cross.size) < 0.1]
    parts[graph.rows[pick]] = parts[graph.indices[pick]]
    parts, _, _ = run_post_stages(graph, parts, nparts, ("repair",),
                                  weights=mesh.weights)
    log(f"mesh: box_mesh({side}, {side}, {side}) = {graph.n} elements, "
        f"{nparts} shards")
    sharded_parity(check, graph, parts, nparts,
                   np.asarray(mesh.weights, np.float64), devices=4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro import obs
    from repro.compile_cache import use_compile_cache

    log(f"jax {jax.__version__}, {len(devices)} x {devices[0].device_kind}, "
        f"compile cache {use_compile_cache()}")
    obs.set_enabled(True)               # the checks read trace counters
    check = Checks()
    t_all = time.perf_counter()
    if args.chips == 4:
        check(len(devices) == 4, f"{len(devices)} devices visible")
        four_chip_phase(check, SIDE, NPARTS)
    else:
        mesh, ctx = main_phase(check, SIDE, NPARTS)
        kernel_phase(check, mesh, ctx, NPARTS, KERNEL_SIDE, KERNEL_NPARTS)
    log(f"total {time.perf_counter() - t_all:.3f} s")
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
