"""CI benchmark-smoke gate: run the partition_time smoke config and fail
(exit 1) if, against the checked-in BENCH_partition.json baseline,

  * any row's RSB edge cut regresses more than 10%, or
  * the config's TOTAL wall clock regresses more than 25%,

or if the refine-stage invariants fail WITHIN the current run:

  * a refined row's cut exceeds its raw (refine="none") sibling's, or
  * a kway row's cut exceeds its greedy (refine="repair+refine") sibling's
    (the hill-climbing k-way FM must never lose to the greedy sweeps), or
  * a refined row reports disconnected parts, or
  * the greedy post stage's summed wall clock exceeds 15% of the summed
    total, or the kway rows' summed post stage exceeds 25% of their summed
    row totals (summed, not per row: the fastest solve's row is pure
    measurement noise at the ~100 ms post scale of this box),

or if the multilevel-engine contract fails (check_multilevel): the smoke
multilevel row's cut must stay within 5% of the BEST spectral kway cut,
and the checked-in `partition_large` baseline rows must uphold the
headline claim — multilevel wall ≤ half the spectral wall at ≤5% cut
regression with zero disconnected parts.

    PYTHONPATH=src python -m benchmarks.smoke_check [--baseline PATH]

The smoke config (benchmarks/partition_time.py, smoke=True) is the batched
engine, BOTH solver families (lanczos and inverse — inverse-iteration
regressions would be invisible to a lanczos-only gate), both inverse
preconditioners (jacobi and the packed multilevel AMG), pre ∈ {none, rcb}
on a small pebble mesh — fast enough for every push.  Each combination
emits a refine="none" row (raw bisection labels) and a refined row from
ONE solve; rows are matched on (engine, method, pre, precond, refine).
Cut is gated per row (quality regressions are the silent failure mode of
solver refactors); wall clock is gated on the summed config only, with
generous headroom, because per-row timings are too noisy on shared CI
runners but a >25% total blowup means iteration counts exploded or a hot
path fell off its fast route.  The wall measurement is the MIN of three
warm in-process runs after one cold run (the cold run pays the XLA
compiles, which vary wildly across runners; the min-of-3 warm sum is the
box's reproducible algorithmic time — single runs on this class of runner
swing ±25-40%).  The checked-in baseline is measured under IDENTICAL
conditions: `benchmarks.run --json` runs the smoke config in a fresh
subprocess (cold, then warm) three times and keeps the repetition with
the minimal summed wall, so both sides of the gate estimate the same
quantity with the same estimator and the headroom covers regressions,
not measurement noise.
The summed wall clock counts each solve once (refined rows only when the
refine axis is present).

The fault-tolerance gate (check_chaos) rides along too: every
repro.guard.chaos fault class — solver NaNs, empty sign-splits, CG
divergence, stage-deadline expiry, truncated halo plans — is injected
deterministically and must degrade into a full-coverage, connected,
corridor-balanced partition with the degradation visible in the guard
report AND the trace counters (silent absorption fails the gate).

Observability gates (repro.obs) ride on the same invocation:

  * every run writes a JSONL run manifest + a Chrome/Perfetto trace for a
    representative quality-kway pipeline run and VALIDATES the manifest —
    a missing stage span (someone deleted or renamed an `obs.timed` call)
    fails the gate: the drift guard that keeps the traces trustworthy;
  * the per-stage wall SHARES of the warm rows are gated against the
    baseline's recorded `stages` maps: any stage whose share of the row
    wall grew by more than 15 percentage points fails (a stage silently
    eating the pipeline is exactly what total-wall headroom hides).  The
    trace JSON is uploaded as a CI artifact (see .github/workflows/ci.yml)
    so a regression comes with its own flamegraph.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmarks import partition_time

TOLERANCE = 1.10       # per-row: fail if cut > 110% of baseline
WALL_TOLERANCE = 1.25  # total: fail if summed seconds > 125% of baseline
POST_FRACTION = 0.15   # greedy post wall clock ≤ 15% of the summed total
KWAY_POST_FRACTION = 0.25  # summed kway post ≤ 25% of summed kway row wall
STAGE_SHARE_TOLERANCE = 0.15  # per-stage share of wall may grow ≤ 15 points
DIST_CUT_TOL = 1.01    # sharded refined cut must stay within 1% of host
MULTILEVEL_CUT_TOL = 1.05  # multilevel cut ≤ 105% of the spectral cut
MULTILEVEL_WALL_FRACTION = 0.5  # large row: ml wall ≤ half spectral wall


def _key(row) -> tuple:
    # Older baselines predate the precond/refine columns; default to the
    # values the old rows actually measured (jacobi, raw labels).
    return (row["engine"], row["method"], row["pre"],
            row.get("precond", "jacobi"), row.get("refine", "none"))


def _wall_rows(rows) -> list:
    """Rows whose seconds sum to the config's wall clock, counting each
    solve ONCE: the canonical greedy (repair+refine) rows when the refine
    axis exists — the kway rows re-measure the same solve with a different
    post chain — else any refined rows, else all."""
    greedy = [r for r in rows if r.get("refine") == "repair+refine"]
    if greedy:
        return greedy
    refined = [r for r in rows if r.get("refine", "none") != "none"]
    return refined or list(rows)


def measure_smoke() -> tuple[list, list, float]:
    """The gate's estimator: one cold smoke run (it pays the XLA compiles
    and its rows gate the cut), then three warm runs, keeping the one
    with the minimal summed wall.  Returns ``(cold, warm, warm_wall)``;
    ``benchmarks.run --json`` records its baseline with this same call."""
    cold = partition_time.run(smoke=True)
    warm_runs = [partition_time.run(smoke=True) for _ in range(3)]
    warm = min(warm_runs,
               key=lambda rs: sum(r["seconds"] for r in _wall_rows(rs)))
    return cold, warm, sum(r["seconds"] for r in _wall_rows(warm))


def check_refine_invariants(rows, warm_rows=None) -> list:
    """The post-stage contract, asserted within one run: refined cut never
    above raw cut, zero disconnected parts, bounded post wall clock.
    Cut/connectivity come from ``rows`` (deterministic, so the cold run is
    fine); the post-fraction check uses ``warm_rows`` — cold totals are
    dominated by XLA compiles and would make a 15%-of-total bound
    near-vacuous.  Returns failure messages (empty = pass)."""
    failures = []
    raw = {_key(r)[:4]: r for r in rows if r.get("refine", "none") == "none"}
    refined = [r for r in rows if r.get("refine", "none") != "none"]
    for r in refined:
        base = raw.get(_key(r)[:4])
        if base is not None and r["cut"] > base["cut"] + 1e-9:
            failures.append(
                f"refined cut {r['cut']:.0f} > raw {base['cut']:.0f} "
                f"for {_key(r)[:4]}")
        if r.get("disconnected", 0) != 0:
            failures.append(
                f"{r['disconnected']} disconnected part(s) after refine "
                f"for {_key(r)[:4]}")
    # k-way gate: the hill-climbing chain must never lose to the greedy
    # sweeps it is meant to supersede (same solve, same corridor).
    greedy = {_key(r)[:4]: r for r in rows
              if r.get("refine") == "repair+refine"}
    for r in (r for r in rows if r.get("refine") == "repair+kway"):
        base = greedy.get(_key(r)[:4])
        if base is not None and r["cut"] > base["cut"] + 1e-9:
            failures.append(
                f"kway cut {r['cut']:.0f} > greedy {base['cut']:.0f} "
                f"for {_key(r)[:4]}")
    timed = rows if warm_rows is None else warm_rows
    canon = [r for r in timed if r.get("refine") == "repair+refine"] or [
        r for r in timed if r.get("refine", "none") != "none"]
    total = sum(r["seconds"] for r in canon)
    post = sum(r.get("post_seconds", 0.0) for r in canon)
    if canon and total > 0 and post > POST_FRACTION * total:
        failures.append(
            f"post stage {post:.3f}s exceeds {POST_FRACTION:.0%} of "
            f"total {total:.3f}s")
    kway_rows = [r for r in timed if r.get("refine") == "repair+kway"]
    k_total = sum(r["seconds"] for r in kway_rows)
    k_post = sum(r.get("post_seconds", 0.0) for r in kway_rows)
    if kway_rows and k_total > 0 and k_post > KWAY_POST_FRACTION * k_total:
        failures.append(
            f"kway post {k_post:.3f}s exceeds {KWAY_POST_FRACTION:.0%} of "
            f"kway rows' total {k_total:.3f}s")
    return failures


def check_stage_shares(rows, base_rows) -> list:
    """Per-stage wall-share gate: for rows matched on the smoke key, no
    stage's share of that row's summed stage wall may exceed the
    baseline's share by more than STAGE_SHARE_TOLERANCE (absolute).
    Shares, not seconds — runner speed cancels out; a stage quietly
    growing from 5% to 40% of the pipeline does not.  Rows without a
    recorded ``stages`` map (pre-obs baselines) are skipped."""
    failures = []
    base_by_key = {_key(r): r for r in base_rows if r.get("stages")}
    for row in rows:
        if not row.get("stages"):
            continue
        base = base_by_key.get(_key(row))
        if base is None:
            continue
        total = sum(row["stages"].values())
        base_total = sum(base["stages"].values())
        if total <= 0 or base_total <= 0:
            continue
        for stage, secs in row["stages"].items():
            share = secs / total
            base_share = base["stages"].get(stage, 0.0) / base_total
            if share > base_share + STAGE_SHARE_TOLERANCE:
                failures.append(
                    f"stage {stage} is {share:.0%} of wall vs baseline "
                    f"{base_share:.0%} for {_key(row)}")
    return failures


def check_multilevel(rows, large_rows) -> list:
    """The multilevel bisect stage's contract.  In the current smoke run:
    the V-cycle's refined row must exist and its cut must stay within
    MULTILEVEL_CUT_TOL of the BEST batched repair+kway cut (the quality
    claim is "spectral-class cuts", so the gate compares against the
    strongest spectral configuration, not the weakest).  From the recorded
    ``partition_large`` baseline (benchmarks.run --json measures it; the
    rows are too slow to re-run on every push): the headline claim itself —
    multilevel wall ≤ MULTILEVEL_WALL_FRACTION of the spectral wall at
    ≤ MULTILEVEL_CUT_TOL cut with zero disconnected parts — so a baseline
    refresh that silently loses the speedup or the quality fails CI."""
    failures = []
    ml = [r for r in rows if r.get("engine") == "multilevel"
          and r.get("refine") == "repair+kway"]
    if not ml:
        failures.append("no multilevel repair+kway smoke row")
    batched = [r["cut"] for r in rows if r.get("engine") == "batched"
               and r.get("refine") == "repair+kway"]
    if ml and batched:
        best = min(batched)
        for r in ml:
            if r["cut"] > MULTILEVEL_CUT_TOL * best:
                failures.append(
                    f"multilevel cut {r['cut']:.0f} > "
                    f"{MULTILEVEL_CUT_TOL:.2f}x best spectral kway cut "
                    f"{best:.0f}")
    by_bisect = {r.get("bisect"): r for r in large_rows}
    sp = by_bisect.get("rsb-batched")
    mlr = by_bisect.get("multilevel")
    if sp is None or mlr is None:
        failures.append("partition_large baseline is missing an engine row "
                        "(regenerate with benchmarks.run --json)")
        return failures
    if mlr["seconds"] > MULTILEVEL_WALL_FRACTION * sp["seconds"]:
        failures.append(
            f"large-mesh multilevel wall {mlr['seconds']:.2f}s > "
            f"{MULTILEVEL_WALL_FRACTION:.0%} of spectral "
            f"{sp['seconds']:.2f}s")
    if mlr["cut"] > MULTILEVEL_CUT_TOL * sp["cut"]:
        failures.append(
            f"large-mesh multilevel cut {mlr['cut']:.0f} > "
            f"{MULTILEVEL_CUT_TOL:.2f}x spectral {sp['cut']:.0f}")
    if mlr.get("disconnected", 0) != 0:
        failures.append(
            f"large-mesh multilevel row has {mlr['disconnected']} "
            f"disconnected part(s)")
    return failures


def check_manifest(manifest_path: str, trace_path: str) -> list:
    """Write + validate a run manifest for a representative quality-kway
    pipeline run — the drift guard.  A deleted/renamed stage span, an
    empty trace, or a manifest that fails schema validation returns
    failure messages; the Perfetto trace JSON lands at ``trace_path``
    (the CI artifact)."""
    from repro import obs
    from repro.core import PartitionPipeline
    from repro.mesh import pebble_mesh

    if not obs.obs_enabled():
        return ["REPRO_OBS is off — the smoke gate needs the trace "
                "(unset REPRO_OBS or set it to 'on')"]
    mesh = pebble_mesh(8, 8, 8, n_pebbles=3, seed=0)
    ctx = PartitionPipeline(pre="rcb", bisect="rsb-batched",
                            post=("repair", "kway")).run(mesh, 8)
    if ctx.trace is None:
        return ["pipeline run recorded no trace despite REPRO_OBS=on"]
    ctx.export_manifest(manifest_path, name="smoke-quality-kway")
    ctx.export_trace_events(trace_path)
    problems = obs.validate_manifest(manifest_path)
    # Same guard for the multilevel V-cycle's spans (coarsen / coarsest /
    # mlevel:N / finalize) — a second manifest from the same small mesh.
    ml_manifest = manifest_path.replace(".jsonl", "_multilevel.jsonl")
    ml_trace = trace_path.replace(".json", "_multilevel.json")
    ctx = PartitionPipeline(pre="none", bisect="multilevel",
                            post=("repair", "kway")).run(mesh, 8)
    if ctx.trace is None:
        problems.append("multilevel run recorded no trace")
    else:
        ctx.export_manifest(ml_manifest, name="smoke-multilevel")
        ctx.export_trace_events(ml_trace)
        problems += obs.validate_manifest(ml_manifest)
    print(f"manifests {manifest_path}, {ml_manifest} "
          f"({'OK' if not problems else 'INVALID'}), "
          f"traces {trace_path}, {ml_trace}", file=sys.stderr)
    return problems


def check_dist_refine(base_sharded) -> list:
    """Device-resident sharded refinement contract (dist/refine_sharded):

    * BENCH_partition.json must carry recorded ``partition_sharded`` rows
      (host chain + both sharded chains) — a baseline refresh that drops
      the table disables this gate silently otherwise;
    * live re-run: the sharded refined cut stays within DIST_CUT_TOL of
      the host ``repair+refine`` cut from the SAME bisection labels, with
      zero disconnected parts;
    * one-collective-per-sweep: the trace counters on every sharded row
      (recorded AND live) must certify exactly one boundary-label
      all_gather per sweep (``sharded_gathers == sharded_sweeps > 0``)
      with non-zero halo traffic and ``halo_bytes == 4 * halo_words``.
    """
    failures = []
    need = {"sharded/repair+refine", "sharded/repair+refine-sharded",
            "sharded/kway-sharded"}
    have = {r.get("name") for r in base_sharded}
    if not need <= have:
        failures.append(
            f"partition_sharded baseline rows missing {sorted(need - have)}"
            " (regenerate with benchmarks.run --json)")
    rows = partition_time.run_sharded()

    def contract(r, tag):
        out = []
        sweeps, gathers = r.get("sweeps", 0), r.get("gathers", 0)
        if not sweeps or gathers != sweeps:
            out.append(f"{tag}: gathers {gathers:.0f} != sweeps "
                       f"{sweeps:.0f} — the one-collective-per-sweep "
                       "contract is broken")
        if not r.get("halo_words"):
            out.append(f"{tag}: no halo traffic counted")
        elif r.get("halo_bytes") != 4 * r["halo_words"]:
            out.append(f"{tag}: halo_bytes {r.get('halo_bytes', 0):.0f} != "
                       f"4x halo_words {r['halo_words']:.0f}")
        return out

    for src, rs in (("baseline", base_sharded), ("live", rows)):
        by = {r.get("name"): r for r in rs}
        host = by.get("sharded/repair+refine")
        for name in ("sharded/repair+refine-sharded", "sharded/kway-sharded"):
            r = by.get(name)
            if r is None or host is None:
                continue  # missing rows already reported above
            failures.extend(contract(r, f"{src} {name}"))
            if r["cut"] > DIST_CUT_TOL * host["cut"]:
                failures.append(
                    f"{src} {name}: cut {r['cut']:.0f} > "
                    f"{DIST_CUT_TOL:.2f}x host refined {host['cut']:.0f}")
            if src == "live" and r.get("disconnected", 0) != 0:
                failures.append(f"{src} {name}: {r['disconnected']} "
                                "disconnected part(s)")
    return failures


def check_chaos() -> list:
    """The fault-tolerance gate (repro.guard): every injected fault class
    must still yield a full-coverage, connected, corridor-balanced
    labeling, with the degradation visible in BOTH the guard report and
    the trace counters — a fault the guard absorbs silently is as much a
    gate failure as one it cannot absorb.  Deterministic: chaos firing is
    a pure function of the (seed-keyed) site config."""
    import numpy as np

    from repro.core import PartitionPipeline
    from repro.dist import plan_halo_sharding, verify_halo_plan
    from repro.guard import chaos
    from repro.guard.policy import count_disconnected
    from repro.mesh import pebble_mesh

    failures = []
    mesh = pebble_mesh(8, 8, 8, n_pebbles=3, seed=0)
    nparts = 8
    solver_sites = ["solver_nan", "empty_split", "cg_divergence", "deadline"]
    for site in solver_sites:
        # cg_divergence lives in the inverse-iteration outer loop; the
        # other sites corrupt any solver's result at the guard boundary.
        bkw = {"method": "inverse"} if site == "cg_divergence" else {}
        ctx = PartitionPipeline(
            pre="rcb", bisect="rsb-batched", post=("repair", "refine"),
            bisect_kw=bkw, guard=True, guard_kw={"chaos": (site,)},
        ).run(mesh, nparts)
        parts = ctx.parts
        graph = ctx.require_graph()
        tag = f"chaos[{site}]"
        if sorted(np.unique(parts)) != list(range(nparts)):
            failures.append(f"{tag}: labels do not cover 0..{nparts - 1}")
        if count_disconnected(graph, parts, nparts) != 0:
            failures.append(f"{tag}: disconnected parts in output")
        # The corridor is weighted — pebble elements carry 1..2x weights.
        w = np.asarray(mesh.weights, np.float64)
        pw = np.bincount(parts, weights=w, minlength=nparts)
        mean = w.sum() / nparts
        if pw.max() > 1.10 * mean:
            failures.append(
                f"{tag}: weighted imbalance {pw.max() / mean:.3f} > 1.10")
        gr = ctx.report.guard
        if gr is None or gr.fallbacks <= 0:
            failures.append(f"{tag}: guard report shows no fallbacks — "
                            "the fault was not exercised")
        elif ctx.trace is not None:
            traced = ctx.trace.total_counters().get("guard_fallbacks", 0)
            if int(traced) != int(gr.fallbacks):
                failures.append(
                    f"{tag}: trace counter guard_fallbacks={traced:.0f} "
                    f"!= report {gr.fallbacks}")
        if site == "deadline" and (gr is None or not gr.deadline_expired):
            failures.append(f"{tag}: deadline never marked expired")
    # halo_truncate: the plan self-check must catch the dropped export
    # rows and rebuild a plan identical to the clean one.
    ctx = PartitionPipeline(pre="rcb", bisect="rsb-batched",
                            post=("repair", "refine"), guard=True).run(
                                mesh, nparts)
    clean = plan_halo_sharding(ctx.require_graph(), ctx.parts, nparts)
    with chaos.overlay(("halo_truncate",)):
        rebuilt = plan_halo_sharding(ctx.require_graph(), ctx.parts, nparts)
    if verify_halo_plan(rebuilt):
        failures.append("chaos[halo_truncate]: rebuilt plan still invalid")
    if not np.array_equal(rebuilt.export_mask, clean.export_mask):
        failures.append("chaos[halo_truncate]: rebuilt plan differs from "
                        "the clean plan")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="BENCH_partition.json")
    ap.add_argument("--manifest", default="runs/smoke_manifest.jsonl")
    ap.add_argument("--trace", default="runs/smoke_trace.json")
    args = ap.parse_args()

    with open(args.baseline) as f:
        baseline = json.load(f)
    base_rows = baseline.get("partition_time_smoke", [])
    if not base_rows:
        print(f"no partition_time_smoke baseline in {args.baseline}",
              file=sys.stderr)
        return 1

    # cold rows gate the cut; warm: min-of-3 summed wall clock (same
    # estimator as the baseline); the min-sum run's rows also feed the
    # post-fraction invariant
    rows, warm, wall = measure_smoke()
    by_key = {_key(r): r for r in rows}
    failed = False
    for base in base_rows:
        key = _key(base)
        row = by_key.get(key)
        if row is None:
            print(f"MISSING smoke row {key}", file=sys.stderr)
            failed = True
            continue
        ratio = row["cut"] / base["cut"]
        status = "OK" if ratio <= TOLERANCE else "REGRESSION"
        print(f"{status} {key}: cut {row['cut']:.0f} vs baseline "
              f"{base['cut']:.0f} ({ratio:.3f}x)", file=sys.stderr)
        if ratio > TOLERANCE:
            failed = True

    for msg in check_refine_invariants(rows, warm):
        print(f"REFINE-GATE {msg}", file=sys.stderr)
        failed = True

    # Multilevel engine contract: smoke-run quality vs the spectral rows,
    # plus the recorded large-mesh headline claim from the baseline.
    for msg in check_multilevel(rows, baseline.get("partition_large", [])):
        print(f"MULTILEVEL-GATE {msg}", file=sys.stderr)
        failed = True

    # Per-stage wall shares: warm rows against the baseline's stage maps.
    for msg in check_stage_shares(warm, base_rows):
        print(f"STAGE-GATE {msg}", file=sys.stderr)
        failed = True

    # Observability drift guard: manifest must exist, validate, and carry
    # every stage span the recorded config implies.
    for msg in check_manifest(args.manifest, args.trace):
        print(f"OBS-GATE {msg}", file=sys.stderr)
        failed = True

    # Sharded-refinement gate: cut parity with the host chain and the
    # one-all_gather-per-sweep collective contract, on both the recorded
    # partition_sharded baseline and a live re-run.
    for msg in check_dist_refine(baseline.get("partition_sharded", [])):
        print(f"DIST-GATE {msg}", file=sys.stderr)
        failed = True

    # Fault-tolerance gate: every chaos fault class must degrade into a
    # valid partition with the degradation visible in report + counters.
    for msg in check_chaos():
        print(f"CHAOS-GATE {msg}", file=sys.stderr)
        failed = True

    base_wall = sum(r["seconds"] for r in _wall_rows(base_rows))
    if base_wall > 0:
        ratio = wall / base_wall
        status = "OK" if ratio <= WALL_TOLERANCE else "REGRESSION"
        print(f"{status} wall clock: {wall:.2f}s vs baseline "
              f"{base_wall:.2f}s ({ratio:.3f}x)", file=sys.stderr)
        if ratio > WALL_TOLERANCE:
            failed = True
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
