"""Shared benchmark utilities: timing, CSV emission, and the partition
tables' common row columns (extracted once from the pipeline's own
``to_dict`` records instead of per-table by hand)."""

from __future__ import annotations

import time

import jax


def report_cols(report) -> dict:
    """Solver-provenance columns every partition table repeats: geometric
    pre-pass, preconditioner family, multilevel depth, total iterations.
    One extraction point over ``RSBReport.to_dict`` — the tables stop
    cherry-picking attributes by hand."""
    d = report.to_dict()
    return {"pre": d["pre"] or "none", "precond": d["precond"],
            "precond_levels": d["precond_levels"],
            "iters": d["total_iterations"]}


def stage_seconds(ctx) -> dict:
    """Per-stage wall seconds of a pipeline run, keyed by span name
    (``pre:rcb``, ``bisect:rsb-batched``, ``post:repair``, …).  Reads the
    run's trace when one was recorded; falls back to the StageRecords so
    the columns survive ``REPRO_OBS=off``."""
    trace = getattr(ctx, "trace", None)
    if trace is not None:
        return {c.name: c.seconds for c in trace.children}
    return {f"{s.kind}:{s.name}": s.seconds for s in ctx.stages}


def time_fn(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Median wall time (µs) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}")


def host_info() -> dict:
    """The JAX version and the platform of the device the run used."""
    return {"jax": jax.__version__, "device": jax.devices()[0].platform}
