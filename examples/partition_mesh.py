"""The NekRS workflow: mesh → partition → element redistribution, with all
partitioners compared (RSB / RCB / RIB / SFC / random).

    PYTHONPATH=src python examples/partition_mesh.py \
        [--dims NX NY NZ] [--pebbles K] [--nparts P] [--seed S] \
        [--devices N]

Bad sizes go through the guard's validation front door and come back as a
typed diagnostic (exit 2), not a traceback.

``--devices N`` (N > 1) adds the device-resident sharded refinement row
(``rsb_sharded``, dist/refine_sharded) and prints its span tree — one
``sweep:k`` span per collective round with the halo_words/halo_bytes
exchange cost on each.  The default (1 device) keeps the host refinement
path and skips the demo.
"""

import argparse
import os
import sys

# The forced host-device count must reach XLA before jax is (transitively)
# imported below, so peek at --devices ahead of the real argparse run.
_pre = argparse.ArgumentParser(add_help=False)
_pre.add_argument("--devices", default=1)
try:
    _ndev = int(_pre.parse_known_args()[0].devices)
except (ValueError, TypeError):
    _ndev = 1
if _ndev > 1 and "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_ndev}").strip()


from repro import obs
from repro.compile_cache import use_compile_cache
from repro.core import PartitionPipeline, partition, partition_metrics, run_post_stages
from repro.dist.partition_aware import plan_halo_sharding, scatter_features
from repro.guard import GuardError, check_positive_int, validate_mesh, validate_nparts
from repro.mesh import dual_graph, pebble_mesh


def main(argv=None) -> int:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dims", nargs=3, default=[12, 12, 12],
                    metavar=("NX", "NY", "NZ"))
    ap.add_argument("--pebbles", default=5)
    ap.add_argument("--nparts", default=16)
    ap.add_argument("--seed", default=1)
    ap.add_argument("--devices", default=1,
                    help="emulated device count for the sharded-refinement "
                         "demo (default 1 = host path only)")
    args = ap.parse_args(argv)

    try:
        nx, ny, nz = (check_positive_int(name, v) for name, v in
                      zip(("nx", "ny", "nz"), args.dims))
        n_pebbles = check_positive_int("pebbles", args.pebbles, minimum=0)
        seed = check_positive_int("seed", args.seed, minimum=0)
        devices = check_positive_int("devices", args.devices)
        mesh = pebble_mesh(nx, ny, nz, n_pebbles=n_pebbles, warp=0.15,
                           seed=seed)
        nparts = check_positive_int("nparts", args.nparts)
        validate_nparts(nparts, mesh.nelems)
        mesh = validate_mesh(mesh, nparts=nparts)
    except GuardError as err:
        print(err.diagnostic(), file=sys.stderr)
        return 2

    graph = dual_graph(mesh)
    print(f"pebble-bed-like mesh: {mesh.nelems} elements "
          f"({(mesh.weights > 1).sum()} 'flow' elements at 2x weight)")
    print(f"{'method':<12}{'cut':>8}{'volume':>9}{'maxnbr':>7}{'halo':>6}"
          f"{'w-imb':>7}{'disc':>6}")
    # ONE pipeline run yields all three rsb rows: "rsb" is the full pipeline
    # (repair + greedy FM refinement on by default), "rsb_raw" its parts_raw —
    # the same bisection before the post stage — and "rsb_kway" the same
    # bisection refined by the hill-climbing k-way FM chain instead, so the
    # gaps between the rows are exactly what each post chain recovers.
    ctx = PartitionPipeline().run(mesh, nparts)
    parts_kway, _, _ = run_post_stages(graph, ctx.parts_raw, nparts,
                                       ("repair", "kway"),
                                       weights=ctx.weights)
    rows = [("rsb", ctx.parts), ("rsb_kway", parts_kway),
            ("rsb_raw", ctx.parts_raw)]
    sharded_root = None
    if devices > 1:
        # Device-resident sweeps over the same bisection labels: shards
        # exchange ONE fused boundary-label all_gather per sweep; the
        # span tree below prices each round (halo_words/halo_bytes).
        with obs.trace("rsb_sharded") as sharded_root:
            parts_sharded, _, _ = run_post_stages(
                graph, ctx.parts_raw, nparts,
                ("repair", "refine-sharded"), weights=ctx.weights,
                post_kw=dict(sweeps=8))
        rows.insert(1, ("rsb_sharded", parts_sharded))
    rows += [(name, partition(mesh, nparts, partitioner=name))
             for name in ("rcb", "rib", "sfc", "random")]
    for name, parts in rows:
        pm = partition_metrics(graph, parts, nparts, weights=mesh.weights)
        halo = plan_halo_sharding(graph, parts, nparts).halo
        print(f"{name:<12}{pm.edge_cut:>8.0f}{pm.total_volume:>9.0f}"
              f"{pm.max_neighbors:>7}{halo:>6}{pm.weighted_imbalance:>7.3f}"
              f"{pm.disconnected_parts:>6}")

    # element redistribution: permute element data into per-rank blocks —
    # this is the 'apply the partition' step a solver performs before
    # timestepping
    plan = plan_halo_sharding(graph, ctx)
    blocks = scatter_features(plan, mesh.coords)
    print(f"\nredistributed coords into {blocks.shape} per-rank blocks "
          f"(halo capacity {plan.halo} elements/rank)")

    # where the wall clock went: the pipeline run's span tree (name, ms, %
    # of wall, counters) — obs.render of the trace the pipeline recorded
    print("\nrsb pipeline trace (% of wall):")
    print(obs.render(ctx.trace))
    if sharded_root is not None:
        import jax

        print(f"\nsharded refinement trace ({len(jax.devices())} devices, "
              "per-sweep exchange cost):")
        print(obs.render(sharded_root))
    return 0


if __name__ == "__main__":
    sys.exit(main())
