"""Pallas TPU kernel: sparse matvec in transposed-ELL (ELLPACK-T) layout.

TPU adaptation of the paper's Laplacian hot loop (DESIGN.md §2): instead of
CSR rows (GPU-style one-thread-per-row), the adjacency is stored
column-major ELL — `cols_t/vals_t : (w, n)` — so the *node* axis lands on
the 128-wide vector lanes and each of the `w` neighbor slots is one fully
vectorized multiply-accumulate sweep down the sublanes.

The neighbor gather ``x[cols_t]`` runs in XLA before the ``pallas_call``
(Mosaic lowers only 2-D gathers, and a gather of a resident vector by a
``(w, block_n)`` index block is not one), so the kernel streams two
``(w, block_n)`` blocks — gathered values and weights — and reduces them
over the slot axis.  Nothing is resident: scoped VMEM per grid step is
2 inputs × 2 pipeline buffers × w × block_n × 4 B (512 KiB at w = 32,
block_n = 1024) whatever n is, so n is bounded by HBM, not VMEM: the XLA
gather materialises w × n values there (32 MiB at n = 262,144, w = 32).
Compiled for a TPU v5e at w = 32 for n = 262,144 (the level-0 packed
operator of a 64³ box mesh; ``tests/test_tpu_compile.py``) and for
n = 4,194,304 (1 GiB gathered).

Grid: n / block_n column blocks; block_n is a multiple of 128 (lane width)
and w, the padded max degree, is the full sublane extent of each block.

**Batched variant** (`ell_spmv_batched_pallas`): B independent operators —
the level-synchronous RSB engine's leading-batch-dim layout and the packed
`BatchedAMG` level operators — add a leading batch grid dimension whose
block dim is squeezed (``None``), so the last two block dims of every
operand still follow the (8, 128) tiling rule.  Column ids stay
per-problem (no cross-batch offsets), matching the jnp fallback in
`EllLaplacian.adj_apply`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _spmv_kernel(xg_ref, vals_ref, out_ref):
    xg = xg_ref[...].astype(jnp.float32)           # (w, bn) gathered x
    vals = vals_ref[...].astype(jnp.float32)       # (w, bn)
    out_ref[...] = (vals * xg).sum(axis=0, keepdims=True).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ell_spmv_pallas(
    cols_t: jax.Array,    # (w, n) int32
    vals_t: jax.Array,    # (w, n)
    x: jax.Array,         # (n,)
    *,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    w, n = cols_t.shape
    assert n % block_n == 0, (n, block_n)
    xg = jnp.take(x, cols_t, axis=0)               # (w, n), XLA gather
    out = pl.pallas_call(
        _spmv_kernel,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((w, block_n), lambda i: (0, i)),  # gathered x
            pl.BlockSpec((w, block_n), lambda i: (0, i)),  # vals block
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), x.dtype),
        interpret=interpret,
    )(xg, vals_t)
    return out[0]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def ell_spmv_batched_pallas(
    cols_t: jax.Array,    # (B, w, n) int32 — per-problem column ids
    vals_t: jax.Array,    # (B, w, n)
    x: jax.Array,         # (B, n)
    *,
    block_n: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    B, w, n = cols_t.shape
    assert n % block_n == 0, (n, block_n)
    xg = jnp.take_along_axis(
        x, cols_t.reshape(B, -1), axis=-1).reshape(B, w, n)
    out = pl.pallas_call(
        _spmv_kernel,
        grid=(B, n // block_n),
        in_specs=[
            pl.BlockSpec((None, w, block_n), lambda b, i: (b, 0, i)),
            pl.BlockSpec((None, w, block_n), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_n), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, 1, n), x.dtype),
        interpret=interpret,
    )(xg, vals_t)
    return out[:, 0]
