"""repro.dist: the distribution layer.

Three pieces, one story — the paper's partitioner output drives the
framework's communication:

* :mod:`repro.dist.partition_aware` — halo sharding plans; a partition's
  edge cut becomes the all_gather volume of each message-passing sweep.
* :mod:`repro.dist.collectives` — the distributed gather-scatter Laplacian
  (paper §5 under shard_map).
* :mod:`repro.dist.refine_sharded` — device-resident sharded boundary
  refinement over the halo plan: one boundary-label all_gather per sweep,
  Pallas segment-sum gain tables (README: "Sharded refinement").
"""

from repro.dist.collectives import dist_lap_apply_allreduce
from repro.dist.partition_aware import (
    HaloPlan,
    adjacency_matvec_distributed,
    gather_features,
    halo_exchange,
    plan_halo_sharding,
    scatter_features,
    verify_halo_plan,
)
from repro.dist.refine_sharded import (
    FrontierPlan,
    build_frontier_plan,
    kway_sharded_stage,
    refine_sharded_host,
    refine_sharded_stage,
    run_sharded_sweeps,
)

__all__ = [
    "FrontierPlan",
    "HaloPlan",
    "adjacency_matvec_distributed",
    "build_frontier_plan",
    "dist_lap_apply_allreduce",
    "gather_features",
    "halo_exchange",
    "kway_sharded_stage",
    "plan_halo_sharding",
    "refine_sharded_host",
    "refine_sharded_stage",
    "run_sharded_sweeps",
    "scatter_features",
    "verify_halo_plan",
]
