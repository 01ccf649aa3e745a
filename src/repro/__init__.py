"""repro: parRSB (Recursive Spectral Bisection mesh partitioner) in JAX.

A production-oriented, multi-pod JAX framework reproducing and extending

    "parRSB: Exascale Spectral Element Mesh Partitioning"
    (Ratnayaka & Fischer, CS.DC 2026)

Layers
------
core/      the paper's contribution: gather-scatter Laplacians, Lanczos,
           inverse iteration (flexcg + aggregation-AMG), RCB/RIB/SFC
           pre-partitioners, the recursive RSB driver, quality metrics.
mesh/      hex-mesh + graph substrate (dual graphs, generators).
models/    assigned architectures (LM transformers incl. MoE, GNNs, recsys).
dist/      sharding rules, distributed gather-scatter, partition-aware
           message passing.
train/     optimizers, gradient compression, checkpointing, train loop.
kernels/   Pallas TPU kernels (ELL SpMV, embedding-bag, flash attention).
configs/   one config per assigned architecture (+ the paper's own).
launch/    production mesh, multi-pod dry-run, roofline extraction.
"""

__version__ = "1.0.0"
