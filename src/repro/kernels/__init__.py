"""Pallas TPU kernels for the partitioner's compute hot-spots.

ell_spmv/         Laplacian matvec in transposed-ELL layout — the paper's
                  hot loop (Lanczos / CG / AMG smoothing are all matvec-bound).
segment_sum/      batched row-wise segment sum — the (boundary × nparts)
                  connection table of the sharded FM refinement sweep.

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd public
wrapper dispatching on :func:`on_tpu`), ref.py (pure-jnp oracle used by
tests).
"""

import jax


def on_tpu() -> bool:
    """Whether the default backend is a TPU — the ``prefer="auto"``
    dispatch test every ``ops.py`` shares (compiled Pallas on TPU,
    interpret mode or the jnp path elsewhere)."""
    return jax.default_backend() == "tpu"
