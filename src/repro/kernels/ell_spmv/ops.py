"""Public dispatch for the ELL SpMV kernel.

`prefer="auto"` (the default) runs the compiled Pallas kernel on TPU and
the jnp reference path elsewhere — interpret mode is for parity tests
(`prefer="pallas"` off-TPU), not production dispatch.  Same contract as
`segment_sum.ops`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.ell_spmv.kernel import ell_spmv_batched_pallas, ell_spmv_pallas
from repro.kernels.ell_spmv.ref import ell_spmv_batched_ref, ell_spmv_ref



def _pick_block(n: int) -> int:
    for b in (1024, 512, 256, 128):
        if n % b == 0:
            return b
    return 0


def ell_spmv(cols: jax.Array, vals: jax.Array, x: jax.Array, *,
             prefer: str = "auto") -> jax.Array:
    """A·x with row-major ELL inputs (n, w) — transposes to ELLPACK-T and
    dispatches per ``prefer``: "auto" (Pallas on TPU, jnp reference
    elsewhere) | "pallas" (interpret mode off-TPU) | "ref", padding n to
    a lane-aligned block size on the Pallas path."""
    if prefer == "ref" or (prefer == "auto" and not on_tpu()):
        return ell_spmv_ref(cols.T, vals.T, x)
    n, w = cols.shape
    block = _pick_block(n)
    if block == 0:
        n_pad = -(-n // 128) * 128
        cols = jnp.pad(cols, ((0, n_pad - n), (0, 0)))
        vals = jnp.pad(vals, ((0, n_pad - n), (0, 0)))
        xp = jnp.pad(x, (0, n_pad - n))
        out = ell_spmv_pallas(
            cols.T, vals.T, xp, block_n=128, interpret=not on_tpu()
        )
        return out[:n]
    return ell_spmv_pallas(cols.T, vals.T, x, block_n=block, interpret=not on_tpu())


def ell_spmv_batched(cols: jax.Array, vals: jax.Array, x: jax.Array, *,
                     prefer: str = "auto") -> jax.Array:
    """B independent A·x products with row-major ELL inputs (B, n, w) and
    per-problem vectors (B, n) — transposes to (B, w, n) ELLPACK-T and
    dispatches per ``prefer`` (see :func:`ell_spmv`), padding n to a
    lane-aligned block size on the Pallas path."""
    if prefer == "ref" or (prefer == "auto" and not on_tpu()):
        return ell_spmv_batched_ref(cols.swapaxes(-1, -2),
                                    vals.swapaxes(-1, -2), x)
    B, n, w = cols.shape
    block = _pick_block(n)
    if block == 0:
        n_pad = -(-n // 128) * 128
        cols = jnp.pad(cols, ((0, 0), (0, n_pad - n), (0, 0)))
        vals = jnp.pad(vals, ((0, 0), (0, n_pad - n), (0, 0)))
        xp = jnp.pad(x, ((0, 0), (0, n_pad - n)))
        out = ell_spmv_batched_pallas(
            cols.swapaxes(-1, -2), vals.swapaxes(-1, -2), xp,
            block_n=128, interpret=not on_tpu(),
        )
        return out[:, :n]
    return ell_spmv_batched_pallas(
        cols.swapaxes(-1, -2), vals.swapaxes(-1, -2), x,
        block_n=block, interpret=not on_tpu(),
    )


def lap_apply(cols: jax.Array, vals: jax.Array, diag: jax.Array,
              x: jax.Array, *, prefer: str = "auto"):
    return diag * x - ell_spmv(cols, vals, x, prefer=prefer)
