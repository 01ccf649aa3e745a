"""repro: parRSB (Recursive Spectral Bisection mesh partitioner) in JAX.

A JAX mesh and graph partitioner reproducing and extending

    "parRSB: Exascale Spectral Element Mesh Partitioning"
    (Ratnayaka & Fischer, CS.DC 2026)

Layers
------
core/      the paper's contribution: gather-scatter Laplacians, Lanczos,
           inverse iteration (flexcg + aggregation-AMG), RCB/RIB/SFC
           pre-partitioners, the RSB engines, the stage pipeline behind
           ``partition()``, repair and FM refinement, quality metrics.
mesh/      hex-mesh + graph substrate (dual graphs, generators).
dist/      partition-aware halo plans, distributed gather-scatter,
           sharded boundary refinement.
guard/     input validation, solver escalation, typed errors.
obs/       span tree, solver metrics, run manifests.
kernels/   Pallas TPU kernels (ELL SpMV, segment sum).
configs/   the pipeline presets (``configs/parrsb.py``).
analysis/  the AST contract checker (``python -m repro.analysis``).
"""

__version__ = "1.0.0"
