"""Benchmark driver: one module per paper table + kernels + roofline.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME] [--json PATH]

Tables ↔ paper:
  partition_time  — Tables 1–2 (Lanczos vs inverse iteration, RCB pre-pass,
                    batched vs recursive RSB engine)
  weak_scaling    — Table 4 (cube meshes, E/P const, message-size regime)
  quality         — §8 evaluation + §3 baselines (RSB/RCB/RIB/SFC/random),
                    including rsb_* rows for both engines
  kernels         — Pallas kernel micro-benches
  roofline        — §Roofline table from cached dry-run artifacts

``--json PATH`` writes the partition tables (plus an `engine_speedup`
summary row — rsb_batched vs rsb_recursive wall clock — the
`partition_time_smoke` baseline the CI gate compares against, and the
`partition_large` multilevel-vs-spectral head-to-head rows the gate's
check_multilevel reads) to PATH in the BENCH_partition.json layout.

This process never imports JAX: every suite runs in a fresh child, one
after another, so each child has the device to itself (a process that has
initialised JAX holds the chip, and a child that needs it then fails or
hangs).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time

_ROWS = "ROWS="


def _in_child(module: str, call: str, *, cache: bool = True):
    """Run ``benchmarks.<module>.<call>`` in a fresh process; echo its
    stdout (the CSV rows) and return the JSON-decoded result.
    ``cache=False`` leaves the persistent compilation cache off, which is
    how smoke_check runs."""
    code = ("import json, sys\n"
            + ("from repro.compile_cache import use_compile_cache\n"
               "use_compile_cache()\n" if cache else "")
            + f"from benchmarks import {module} as m\n"
            f"rows = m.{call}\n"
            "sys.stdout.flush()\n"
            f"print({_ROWS!r} + json.dumps(rows, default=str))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=dict(os.environ))
    sys.stderr.write(out.stderr)
    rows, found = None, False
    for line in out.stdout.splitlines():
        if line.startswith(_ROWS):
            rows, found = json.loads(line[len(_ROWS):]), True
        else:
            print(line)
    if out.returncode != 0 or not found:
        raise SystemExit(f"benchmark child {module}.{call} failed "
                         f"(exit {out.returncode})")
    return rows


def _smoke_baseline_rows(repeats: int = 3) -> list:
    """Measure the partition_time smoke rows the way benchmarks.smoke_check
    will gate them: a FRESH process per repetition running the gate's exact
    recipe (``smoke_check.measure_smoke``: one cold run that pays the XLA
    compiles, then the min-sum of three warm runs), keeping the repetition
    with the minimal summed wall clock.  Matching the estimator on both
    sides is the whole point: per-row minima across repetitions would
    bound below anything a single run can reach, and measuring in the warm
    tail of the full suite reads ~25-30% faster than any fresh smoke_check
    process — either way the wall gate's headroom would be spent on
    methodology, not regressions.  The gate runs without the persistent
    compilation cache, so these children do too."""
    runs = [_in_child("smoke_check", "measure_smoke()[1:]", cache=False)
            for _ in range(repeats)]
    return min(runs, key=lambda warm_wall: warm_wall[1])[0]


def _engine_pre_table(partition_rows) -> list:
    """engine × (method, pre, precond) comparison: seconds / iters / cut.

    One line per (method, pre, precond) combination with the batched and
    recursive wall clocks side by side — the at-a-glance view of where the
    level-synchronous engine and the multilevel solver schedule pay off.
    """
    if not partition_rows:
        return []
    # One solve emits refine="none", greedy, and kway rows; the greedy
    # (repair+refine) row is the canonical full-pipeline measurement (old
    # baselines have no axis).
    canon = [r for r in partition_rows
             if r.get("refine") == "repair+refine"] or [
        r for r in partition_rows if r.get("refine", "none") != "none"]
    cells: dict = {}
    for r in canon or partition_rows:
        key = (r["method"], r["pre"], r.get("precond", "jacobi"))
        cells.setdefault(key, {})[r["engine"]] = r
    lines = ["# engine×pre comparison (seconds | iters | cut)"]
    header = (f"# {'method':<8} {'pre':<5} {'precond':<8} "
              f"{'batched':>22} {'recursive':>22} {'speedup':>8}")
    lines.append(header)
    for key in sorted(cells):
        method, pre, precond = key
        row = cells[key]

        def cell(engine):
            r = row.get(engine)
            if r is None:
                return f"{'—':>22}"
            return f"{r['seconds']:7.2f}s {r['iters']:4d}it {r['cut']:7.0f}"

        speed = "—"
        if "batched" in row and "recursive" in row and row["batched"]["seconds"]:
            speed = f"{row['recursive']['seconds'] / row['batched']['seconds']:.2f}x"
        lines.append(f"# {method:<8} {pre:<5} {precond:<8} "
                     f"{cell('batched')} {cell('recursive')} {speed:>8}")
    return lines


def _engine_speedup(quality_rows, partition_rows) -> dict:
    """rsb_batched vs rsb_recursive wall-clock, per suite.  Refine-axis
    duplicate rows (raw labels and the kway re-refinement, both re-recorded
    from the same solve) are excluded so a solve is counted once."""
    quality_rows = [r for r in quality_rows
                    if not str(r.get("name", "")).endswith(("_raw", "_kway"))]
    partition_rows = [r for r in partition_rows
                      if r.get("refine", "x") == "repair+refine"] or [
        r for r in partition_rows if r.get("refine", "x") != "none"
    ] or partition_rows
    out: dict = {}
    q_b = sum(r["seconds"] for r in quality_rows if r.get("engine") == "batched")
    q_r = sum(r["seconds"] for r in quality_rows
              if r.get("engine") == "recursive")
    if q_b and q_r:
        out["quality_rsb_batched_seconds"] = q_b
        out["quality_rsb_recursive_seconds"] = q_r
        out["quality_speedup"] = q_r / q_b
    p_b = sum(r["seconds"] for r in partition_rows
              if r.get("engine") == "batched")
    p_r = sum(r["seconds"] for r in partition_rows
              if r.get("engine") == "recursive")
    if p_b and p_r:
        out["partition_time_batched_seconds"] = p_b
        out["partition_time_recursive_seconds"] = p_r
        out["partition_time_speedup"] = p_r / p_b
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default=None,
                    choices=["partition", "partition_time", "weak_scaling",
                             "quality", "kernels", "roofline"])
    ap.add_argument("--dryrun-dir", default="runs/dryrun")
    ap.add_argument("--json", default=None,
                    help="write partition tables to this BENCH json path")
    args = ap.parse_args()
    if args.json and args.only not in (None, "partition"):
        # The BENCH json is the CI gate's baseline; writing it from a run
        # that skipped either partition suite would clobber it with empty
        # tables and break benchmarks.smoke_check on the next push.
        ap.error("--json requires both partition tables; drop --only or "
                 "use --only partition")

    print("name,us_per_call,derived")
    t0 = time.time()

    def want(name):
        if args.only == "partition":  # both tables the BENCH json records
            return name in ("quality", "partition_time")
        return args.only is None or args.only == name

    quality_rows: list = []
    partition_rows: list = []
    smoke_rows: list = []
    large_rows: list = []
    sharded_rows: list = []
    if want("quality"):
        quality_rows = _in_child("quality", f"run(full={args.full})")
    if want("partition_time"):
        partition_rows = _in_child("partition_time", f"run(full={args.full})")
        for line in _engine_pre_table(partition_rows):
            print(line)
        if args.json:
            # Fresh-process min-of-3, matching smoke_check's measurement
            # conditions exactly — see _smoke_baseline_rows.
            smoke_rows = _smoke_baseline_rows()
            # Large-mesh engine head-to-head behind the multilevel claim;
            # smoke_check gates these recorded rows instead of re-running
            # the ~10x mesh on every push.
            large_rows = _in_child("partition_time", "run_large()")
            # Device-resident sharded refinement vs the host chain from
            # the same bisection — check_dist_refine gates cut parity and
            # the one-collective-per-sweep contract on these rows.
            sharded_rows = _in_child("partition_time", "run_sharded()")
    for name in ("weak_scaling", "kernels"):
        if want(name):
            _in_child(name, f"run(full={args.full})")
    if want("roofline"):
        _in_child("roofline_table", f"run({args.dryrun_dir!r})")

    if args.json:
        payload = {
            "date": time.strftime("%Y-%m-%d"),
            "host": {"platform": platform.platform(),
                     "python": platform.python_version(),
                     **_in_child("bench_util", "host_info()")},
            "quality": quality_rows,
            "partition_time": partition_rows,
            "partition_time_smoke": smoke_rows,
            "partition_large": large_rows,
            "partition_sharded": sharded_rows,
            "engine_speedup": _engine_speedup(quality_rows, partition_rows),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)
    print(f"# benchmarks completed in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
