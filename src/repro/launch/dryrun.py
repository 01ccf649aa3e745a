import os
# A CPU-only compile study: this overwrites XLA_FLAGS at import to fake
# 512 host devices, so it is never a chip entry point (chip_smoke.py is).
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract memory / cost / roofline evidence.

The two lines above MUST precede any jax import — jax locks the device
count at first initialization (see the assignment's MULTI-POD DRY-RUN §0).

Methodology (two compiles per cell, both recorded):
  * EXEC compile — scan-over-layers, exactly the production step.  Its
    `memory_analysis()` is the memory-fit evidence (loop temps = one live
    layer).  XLA's `cost_analysis()` counts a while-loop body ONCE, so
    exec FLOPs understate per-step work — hence:
  * PROFILE compile — layers unrolled.  Its `cost_analysis()` FLOPs/bytes
    and HLO collective census are the per-step roofline inputs.

Usage:
    python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out runs/dryrun
Each cell writes runs/dryrun/<arch>__<shape>__<mesh>.json.
"""

import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs import all_cells, get_arch
from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import collective_wire_bytes, roofline


def _compile(cell, mesh):
    with jax.set_mesh(mesh):
        jitted = jax.jit(
            cell.fn,
            in_shardings=cell.in_specs,
            out_shardings=cell.out_specs,
            donate_argnums=cell.donate(),
        )
        lowered = jitted.lower(*cell.abstract_args)
        compiled = lowered.compile()
    return lowered, compiled


PROFILE_CAP = 6   # unroll directly up to this depth; layer-diff beyond


def _n_layers_of(arch_id: str) -> int | None:
    arch = get_arch(arch_id)
    cfg = arch.make_smoke_config()
    full = arch.make_config()
    return getattr(full, "n_layers", None)


def _census(compiled, n_dev):
    cost = compiled.cost_analysis()
    coll = collective_wire_bytes(compiled.as_text(), n_dev)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": coll.total_wire_bytes,
        "per_op": dict(coll.per_op),
        "counts": dict(coll.counts),
    }


def _profile_census(arch_id, shape_name, mesh, n_dev):
    """Per-step FLOPs/bytes/collectives with unrolled layers.

    Deep models (> PROFILE_CAP layers) are profiled by LAYER DIFFERENCING:
    compile 2- and 4-layer unrolled variants; Q(L) = c + m·L is exact since
    layers are identical, so Q(n) = Q(2) + (n−2)·(Q(4)−Q(2))/2.
    """
    L = _n_layers_of(arch_id)
    if L is None or L <= PROFILE_CAP:
        cell = build_cell(arch_id, shape_name, mesh, unroll=True)
        _, c = _compile(cell, mesh)
        return _census(c, n_dev), {"profile_method": "unrolled-full"}
    qs = {}
    for l in (2, 4):
        cell = build_cell(arch_id, shape_name, mesh, unroll=True, n_layers=l)
        _, c = _compile(cell, mesh)
        qs[l] = _census(c, n_dev)

    def lerp(key):
        m = (qs[4][key] - qs[2][key]) / 2.0
        return qs[2][key] + m * (L - 2)

    out = {k: lerp(k) for k in ("flops", "bytes", "wire")}
    out["per_op"] = {
        k: qs[2]["per_op"][k]
        + (qs[4]["per_op"][k] - qs[2]["per_op"][k]) / 2.0 * (L - 2)
        for k in qs[2]["per_op"]
    }
    out["counts"] = {
        k: int(round(qs[2]["counts"][k]
                     + (qs[4]["counts"][k] - qs[2]["counts"][k]) / 2.0 * (L - 2)))
        for k in qs[2]["counts"]
    }
    return out, {"profile_method": f"layer-diff(2,4)->L={L}"}


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True, profile: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = int(np.prod(mesh.devices.shape))

    # --- EXEC compile: production scan step → memory evidence ---
    t0 = time.perf_counter()
    cell = build_cell(arch_id, shape_name, mesh, unroll=False)
    _, compiled = _compile(cell, mesh)
    t_exec = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(ma.peak_memory_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    live = (mem["argument_bytes"] + mem["output_bytes"] + mem["temp_bytes"]
            - mem["alias_bytes"])

    # --- PROFILE: FLOPs / bytes / collective census (per-step truth) ---
    if profile:
        t1 = time.perf_counter()
        census, pmeta = _profile_census(arch_id, shape_name, mesh, n_dev)
        t_prof = time.perf_counter() - t1
    else:
        t_prof = 0.0
        census, pmeta = _census(compiled, n_dev), {"profile_method": "exec-scan"}
    cost = {"flops": census["flops"], "bytes accessed": census["bytes"]}

    class _Coll:
        def row(self):
            return {
                "wire_bytes": census["wire"],
                "counts": census["counts"],
                "bytes_by_kind": {k: v for k, v in census["per_op"].items() if v},
            }

    coll = _Coll()
    from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS, Roofline

    ct = census["flops"] / PEAK_FLOPS
    mt = census["bytes"] / HBM_BW
    lt = census["wire"] / LINK_BW
    terms = {"compute": ct, "memory": mt, "collective": lt}
    dominant = max(terms, key=terms.get)
    total_flops = census["flops"] * n_dev
    bound = max(ct, mt, lt)
    rl = Roofline(
        flops_per_dev=census["flops"], bytes_per_dev=census["bytes"],
        wire_bytes_per_dev=census["wire"], compute_s=ct, memory_s=mt,
        collective_s=lt, dominant=dominant, model_flops=cell.model_flops,
        useful_fraction=(cell.model_flops / total_flops) if total_flops else 0.0,
        roofline_fraction=(ct / bound) if bound > 0 else 0.0,
    )

    record = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "kind": cell.kind,
        "notes": cell.notes,
        "exec_compile_s": round(t_exec, 2),
        "profile_compile_s": round(t_prof, 2),
        "memory_analysis": mem,
        "live_bytes_per_device": int(live),
        "fits_16gb": bool(live < 16e9),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if k in ("flops", "bytes accessed")},
        "collectives": coll.row(),
        "roofline": rl.row(),
        "status": "ok",
        **pmeta,
    }
    if verbose:
        print(f"== {arch_id} × {shape_name} × {record['mesh']} ==")
        print(f"  memory_analysis(exec): {mem}")
        print(f"  live/device: {live/1e9:.2f} GB  fits16GB={record['fits_16gb']}")
        print(f"  cost_analysis(profile): flops={cost.get('flops', 0):.3e} "
              f"bytes={cost.get('bytes accessed', 0):.3e}")
        print(f"  collectives: {coll.row()}")
        print(f"  roofline: compute={rl.compute_s:.4e}s memory={rl.memory_s:.4e}s "
              f"collective={rl.collective_s:.4e}s dominant={rl.dominant} "
              f"useful={rl.useful_fraction:.3f}")
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-profile", action="store_true",
                    help="skip the unrolled profile compile (faster)")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    if args.all:
        targets = [(a, s) for a, s, _, skip in all_cells() if skip is None]
        skipped = [(a, s, skip) for a, s, _, skip in all_cells() if skip]
    elif args.arch and args.shape is None:
        arch = get_arch(args.arch)
        targets = [(args.arch, s) for s, c, skip in arch.cells() if skip is None]
        skipped = [(args.arch, s, skip) for s, c, skip in arch.cells() if skip]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        targets = [(args.arch, args.shape)]
        skipped = []

    for a, s, reason in skipped:
        rec = {"arch": a, "shape": s, "status": "skip", "reason": reason}
        with open(os.path.join(args.out, f"{a}__{s}__skip.json"), "w") as f:
            json.dump(rec, f, indent=1)
        print(f"SKIP {a} × {s}: {reason}")

    failures = 0
    for a, s in targets:
        for mp in meshes:
            tag = "2x16x16" if mp else "16x16"
            path = os.path.join(args.out, f"{a}__{s}__{tag}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"cached {a} × {s} × {tag}")
                continue
            try:
                rec = run_cell(a, s, multi_pod=mp, profile=not args.no_profile)
            except Exception as e:  # record, keep sweeping
                failures += 1
                rec = {
                    "arch": a, "shape": s, "mesh": tag, "status": "fail",
                    "error": f"{type(e).__name__}: {e}",
                    "trace": traceback.format_exc()[-2000:],
                }
                print(f"FAIL {a} × {s} × {tag}: {e}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            jax.clear_caches()  # bound compile-cache memory across the sweep
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")
    print("dry-run complete")


if __name__ == "__main__":
    main()
