import os
# A CPU-only compile study: this overwrites XLA_FLAGS at import to fake
# 512 host devices, so it is never a chip entry point (chip_smoke.py is).
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimb driver — compiles the OPTIMIZED variants of the three
chosen cells and extracts the same census as the baseline dry-run:

  gnn  — graphcast × ogb_products: partition-aware halo shard_map step
          (full 2.46M-node scale, RCB plan) + RSB-vs-RCB-vs-random halo
          quality study at 262k nodes (collective volume ∝ edge cut).
  moe  — deepseek-moe-16b × train_4k: shard_map expert-parallel dispatch
          (local routing + all-to-all) vs the pjit einsum baseline.
  lm   — mistral-large-123b × train_4k: Megatron-TP baseline
          (seq_shard=False) vs sequence-parallel default.

    PYTHONPATH=src python -m benchmarks.hillclimb --exp gnn --out runs/perf
"""

import argparse
import dataclasses
import json
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.cells import build_cell
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import HBM_BW, LINK_BW, PEAK_FLOPS, collective_wire_bytes


def census_of(compiled, n_dev):
    cost = compiled.cost_analysis()
    coll = collective_wire_bytes(compiled.as_text(), n_dev)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "wire": coll.total_wire_bytes,
        "bytes_by_kind": {k: v for k, v in coll.per_op.items() if v},
        "counts": dict(coll.counts),
    }


def add_terms(rec):
    rec["compute_s"] = rec["flops"] / PEAK_FLOPS
    rec["memory_s"] = rec["bytes"] / HBM_BW
    rec["collective_s"] = rec["wire"] / LINK_BW
    terms = {k: rec[f"{k}_s"] for k in ("compute", "memory", "collective")}
    rec["dominant"] = max(terms, key=terms.get)
    return rec


def _compile_cell(cell, mesh):
    with jax.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_specs,
                         out_shardings=cell.out_specs,
                         donate_argnums=cell.donate())
        return jitted.lower(*cell.abstract_args).compile()


def exp_moe(out):
    """shard_map EP dispatch for deepseek-moe-16b × train_4k (single pod)."""
    mesh = make_production_mesh(multi_pod=False)
    n_dev = 256
    result = {"exp": "moe", "variant": "shardmap-ep"}
    # exec compile (memory)
    cell = build_cell("deepseek-moe-16b", "train_4k", mesh, moe_impl="shardmap")
    c = _compile_cell(cell, mesh)
    ma = c.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    result["live_bytes_per_device"] = int(live)
    jax.clear_caches()
    # profile via layer diff
    qs = {}
    for l in (2, 4):
        pc = build_cell("deepseek-moe-16b", "train_4k", mesh, unroll=True,
                        n_layers=l, moe_impl="shardmap")
        qs[l] = census_of(_compile_cell(pc, mesh), n_dev)
        jax.clear_caches()
    L = 28
    rec = {k: qs[2][k] + (qs[4][k] - qs[2][k]) / 2 * (L - 2)
           for k in ("flops", "bytes", "wire")}
    rec["bytes_by_kind"] = {
        k: qs[2]["bytes_by_kind"].get(k, 0.0)
        + (qs[4]["bytes_by_kind"].get(k, 0.0)
           - qs[2]["bytes_by_kind"].get(k, 0.0)) / 2 * (L - 2)
        for k in set(qs[2]["bytes_by_kind"]) | set(qs[4]["bytes_by_kind"])
    }
    result.update(add_terms(rec))
    _write(out, "moe_shardmap.json", result)


def exp_lm(out):
    """Megatron-TP baseline (no SP) for mistral-large × train_4k."""
    mesh = make_production_mesh(multi_pod=False)
    n_dev = 256
    result = {"exp": "lm", "variant": "tp-baseline-no-sp"}
    cell = build_cell("mistral-large-123b", "train_4k", mesh, seq_shard=False)
    c = _compile_cell(cell, mesh)
    ma = c.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    result["live_bytes_per_device"] = int(live)
    jax.clear_caches()
    qs = {}
    for l in (2, 4):
        pc = build_cell("mistral-large-123b", "train_4k", mesh, unroll=True,
                        n_layers=l, seq_shard=False)
        qs[l] = census_of(_compile_cell(pc, mesh), n_dev)
        jax.clear_caches()
    L = 88
    rec = {k: qs[2][k] + (qs[4][k] - qs[2][k]) / 2 * (L - 2)
           for k in ("flops", "bytes", "wire")}
    result.update(add_terms(rec))
    _write(out, "lm_tp_baseline.json", result)


def _halo_cell(cfg, plan, d_feat, d_out, mesh):
    """Build the shard_map halo train step for a HaloPlan."""
    from repro.models.gnn.graphcast import init_graphcast
    from repro.models.gnn.halo import graphcast_halo_loss, make_halo_batch_abstract
    from repro.train.optimizer import AdamWConfig, abstract_opt_state, adamw_update

    axis = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    hbatch = make_halo_batch_abstract(plan, d_feat, d_out)
    params_abs = jax.eval_shape(lambda: init_graphcast(cfg, jax.random.PRNGKey(0)))
    opt_abs = abstract_opt_state(params_abs)
    bspec = jax.tree_util.tree_map(lambda _: P(axis), hbatch)
    pspec = jax.tree_util.tree_map(lambda _: P(), params_abs)

    def loss_fn(params, hb):
        fn = jax.shard_map(
            lambda p, b: graphcast_halo_loss(
                cfg, p, jax.tree_util.tree_map(lambda x: x[0], b), axis
            )[None],
            in_specs=(pspec, bspec), out_specs=P(axis), check_vma=False,
        )
        return fn(params, hb).mean()

    def step(params, opt_state, hb):
        l, grads = jax.value_and_grad(loss_fn)(params, hb)
        params, opt_state, _ = adamw_update(AdamWConfig(lr=1e-4), grads,
                                            opt_state, params)
        return params, opt_state, l

    return step, (params_abs, opt_abs, hbatch), (pspec, {"m": pspec, "v": pspec, "count": P()}, bspec)


def exp_gnn(out, *, full_side: int = 135, study_side: int = 64):
    """Partition-aware halo message passing for graphcast × ogb_products."""
    from repro.configs import get_arch
    from repro.core.rcb import rcb_parts
    from repro.dist.partition_aware import plan_halo_sharding
    from repro.mesh.graphs import grid_coords_3d, stencil_graph_3d

    mesh = make_production_mesh(multi_pod=False)
    n_dev = 256
    result = {"exp": "gnn", "variant": "halo-shardmap-rcb",
              "graph": f"stencil26 {full_side}^3"}

    t0 = time.perf_counter()
    g = stencil_graph_3d(full_side, full_side, full_side)
    coords = grid_coords_3d(full_side, full_side, full_side)
    parts = rcb_parts(coords, n_dev)
    plan = plan_halo_sharding(g, parts, n_dev, pad_to=8)
    result["plan"] = plan.stats()
    result["plan_seconds"] = round(time.perf_counter() - t0, 1)
    print("plan:", result["plan"], flush=True)

    arch = get_arch("graphcast")
    base_cfg = arch.make_config(d_in=100)
    qs = {}
    for l in (2, 4):
        cfg = dataclasses.replace(base_cfg, n_layers=l, unroll=True)
        step, abstract, specs = _halo_cell(cfg, plan, 100, base_cfg.n_vars, mesh)
        out_specs = (specs[0], specs[1], P())   # params, opt, scalar loss
        with jax.set_mesh(mesh):
            compiled = jax.jit(step, in_shardings=specs, out_shardings=out_specs,
                               donate_argnums=(0, 1)).lower(*abstract).compile()
        qs[l] = census_of(compiled, n_dev)
        if l == 2:
            ma = compiled.memory_analysis()
            # memory: exec==profile here (2-layer); scale residual storage
            result["live_bytes_per_device_2layer"] = int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            )
        jax.clear_caches()
    L = base_cfg.n_layers
    rec = {k: qs[2][k] + (qs[4][k] - qs[2][k]) / 2 * (L - 2)
           for k in ("flops", "bytes", "wire")}
    result.update(add_terms(rec))
    _write(out, "gnn_halo_rcb.json", result)

    # --- partition-quality study at reduced scale: RSB vs RCB vs random ---
    from repro.core import partition_metrics
    from repro.core.rsb import rsb_partition_graph

    gs = stencil_graph_3d(study_side, study_side, study_side)
    cs = grid_coords_3d(study_side, study_side, study_side)
    study = {"graph": f"stencil26 {study_side}^3", "n_shards": n_dev}
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    p_rsb, rep = rsb_partition_graph(gs, n_dev, coords=cs, pre="rcb", tol=1e-3)
    study["rsb_seconds"] = round(time.perf_counter() - t0, 1)
    for name, parts_s in (
        ("rsb", p_rsb),
        ("rcb", rcb_parts(cs, n_dev)),
        ("random", rng.permutation(np.arange(gs.n) % n_dev)),
    ):
        pl = plan_halo_sharding(gs, parts_s, n_dev, pad_to=8)
        pm = partition_metrics(gs, parts_s, n_dev)
        study[name] = {"halo": pl.halo, "cut": pm.edge_cut,
                       "gather_words_per_col": pl.collective_words_per_feature,
                       "max_nbrs": pm.max_neighbors}
        print(name, study[name], flush=True)
    _write(out, "gnn_partition_study.json", study)


def _write(out, name, rec):
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, name), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"wrote {name}: "
          f"{ {k: v for k, v in rec.items() if not isinstance(v, dict)} }")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", required=True, choices=["moe", "gnn", "lm"])
    ap.add_argument("--out", default="runs/perf")
    args = ap.parse_args()
    {"moe": exp_moe, "gnn": exp_gnn, "lm": exp_lm}[args.exp](args.out)


if __name__ == "__main__":
    main()
