"""Pallas TPU kernel: batched row-wise segment sum (connection table).

The sharded-refinement hot loop (DESIGN: `dist/refine_sharded.py`) needs,
per sweep, the (boundary × nparts) *connection-weight table* of every
shard's frontier: ``conn[i, q] = Σ_k w[i, k] · [label[col[i, k]] == q]``.
That is a segment sum over the part axis, one segment per part, with the
segment ids gathered through the ELL adjacency.

Layout differs from `ell_spmv` deliberately: there the *node* axis rides
the 128 lanes (output is a vector); here the output is a table whose lane
axis is ``nparts`` (padded to 128), so ELL rows stay row-major —
``lab/wts : (B, w)`` blocked as ``(block_b, w)`` on the sublane axis —
and each of the ``w`` neighbor slots is one vectorized compare-accumulate
sweep into the ``(block_b, npad)`` accumulator.

The label gather ``labels[cols]`` runs in XLA before the ``pallas_call``
(Mosaic lowers only 2-D gathers), so the kernel receives the gathered
``(B, w)`` int32 segment ids and nothing stays resident: scoped VMEM per
grid step is two ``(block_b, w)`` input blocks and one
``(block_b, npad)`` f32 output block, double-buffered, independent of the
label-space size.

Grid: B / block_b row blocks; the **batched variant** adds a leading
shard-group dimension with a squeezed (``None``) block dim — one launch
computes every shard's frontier table, which is what makes the
refinement sweep a single kernel launch between collectives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segsum_kernel(lab_ref, wts_ref, out_ref):
    lab = lab_ref[...]                           # (bn, w) gathered seg ids
    wts = wts_ref[...].astype(jnp.float32)       # (bn, w)
    bn, npad = out_ref.shape
    iota = jax.lax.broadcasted_iota(lab.dtype, (1, npad), 1)
    acc = jnp.zeros((bn, npad), jnp.float32)
    for k in range(lab.shape[1]):                # w is small and static
        onehot = (lab[:, k:k + 1] == iota).astype(jnp.float32)
        acc = acc + wts[:, k:k + 1] * onehot
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("nparts_pad", "block_b", "interpret"))
def segment_sum_pallas(
    labels: jax.Array,     # (m,) int32 — segment id per combined-space node
    cols: jax.Array,       # (B, w) int32 — indices into labels
    wts: jax.Array,        # (B, w) f32  — padding entries carry weight 0
    *,
    nparts_pad: int,       # output segments, padded to a lane multiple
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, w = cols.shape
    assert B % block_b == 0, (B, block_b)
    lab = jnp.take(labels, cols, axis=0)         # (B, w), XLA gather
    return pl.pallas_call(
        _segsum_kernel,
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, w), lambda i: (i, 0)),  # seg id rows
            pl.BlockSpec((block_b, w), lambda i: (i, 0)),  # wts rows
        ],
        out_specs=pl.BlockSpec((block_b, nparts_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nparts_pad), jnp.float32),
        interpret=interpret,
    )(lab, wts)


@functools.partial(jax.jit,
                   static_argnames=("nparts_pad", "block_b", "interpret"))
def segment_sum_batched_pallas(
    labels: jax.Array,     # (G, m) int32 — per-problem label vectors
    cols: jax.Array,       # (G, B, w) int32
    wts: jax.Array,        # (G, B, w) f32
    *,
    nparts_pad: int,
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:
    G, B, w = cols.shape
    assert B % block_b == 0, (B, block_b)
    lab = jnp.take_along_axis(
        labels, cols.reshape(G, -1), axis=1).reshape(G, B, w)
    return pl.pallas_call(
        _segsum_kernel,
        grid=(G, B // block_b),
        in_specs=[
            pl.BlockSpec((None, block_b, w), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, block_b, w), lambda g, i: (g, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_b, nparts_pad),
                               lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, B, nparts_pad), jnp.float32),
        interpret=interpret,
    )(lab, wts)
