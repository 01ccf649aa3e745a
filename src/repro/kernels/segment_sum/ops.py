"""Public wrappers for the segment-sum (connection table) kernel.

Dispatch policy differs from `ell_spmv`: the table build sits inside the
sharded-refinement sweep (called once per sweep, per shard group, under
``shard_map``), where Pallas *interpret* mode would dominate the sweep
wall clock off-TPU.  So ``prefer="auto"`` routes to the compiled Pallas
kernel on TPU and, everywhere else, to a jitted jnp transcription of the
kernel's own slot-loop algorithm (``_xla_loop``) — w accumulations into a
resident (B, nparts) table, never materializing the (B, w, nparts)
one-hot that makes the naive oracle 10–20× slower than even a NumPy
scatter build.  ``prefer="pallas"`` forces the kernel (interpret mode
off-TPU) for parity tests and microbenches; ``prefer="ref"`` is the
naive oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.segment_sum.kernel import (
    segment_sum_batched_pallas,
    segment_sum_pallas,
)
from repro.kernels.segment_sum.ref import (
    connection_table_batched_ref,
    connection_table_ref,
)



def _pick_block_rows(b: int) -> int:
    """Largest power-of-two row block (≤ 256, ≥ 8 sublanes) dividing b."""
    for blk in (256, 128, 64, 32, 16, 8):
        if b % blk == 0:
            return blk
    return 8


@functools.partial(jax.jit, static_argnames="nparts")
def _xla_loop(labels, cols, wts, *, nparts: int):
    """The kernel's unrolled slot loop in pure jnp — the off-TPU
    production path (w is small and static, so the loop stays fused)."""
    lab = jnp.take(labels, cols, axis=0)                     # (B, w)
    iota = jnp.arange(nparts, dtype=lab.dtype)[None, :]
    acc = jnp.zeros((cols.shape[0], nparts), jnp.float32)
    for k in range(cols.shape[1]):
        onehot = (lab[:, k][:, None] == iota).astype(jnp.float32)
        acc = acc + wts[:, k][:, None].astype(jnp.float32) * onehot
    return acc


@functools.partial(jax.jit, static_argnames="nparts")
def _xla_loop_batched(labels, cols, wts, *, nparts: int):
    return jax.vmap(
        lambda l, c, v: _xla_loop(l, c, v, nparts=nparts)
    )(labels, cols, wts)


_ref_jit = jax.jit(connection_table_ref, static_argnames="nparts")
_ref_batched_jit = jax.jit(connection_table_batched_ref,
                           static_argnames="nparts")


@functools.partial(jax.jit, static_argnames=("nparts", "interpret"))
def _pallas_padded(labels, cols, wts, *, nparts: int, interpret: bool):
    B, _ = cols.shape
    npad = -(-nparts // 128) * 128
    bpad = -(-B // 8) * 8
    if bpad != B:
        cols = jnp.pad(cols, ((0, bpad - B), (0, 0)))
        wts = jnp.pad(wts, ((0, bpad - B), (0, 0)))
    out = segment_sum_pallas(labels, cols, wts, nparts_pad=npad,
                             block_b=_pick_block_rows(bpad),
                             interpret=interpret)
    return out[:B, :nparts]


@functools.partial(jax.jit, static_argnames=("nparts", "interpret"))
def _pallas_batched_padded(labels, cols, wts, *, nparts: int,
                           interpret: bool):
    _, B, _ = cols.shape
    npad = -(-nparts // 128) * 128
    bpad = -(-B // 8) * 8
    if bpad != B:
        cols = jnp.pad(cols, ((0, 0), (0, bpad - B), (0, 0)))
        wts = jnp.pad(wts, ((0, 0), (0, bpad - B), (0, 0)))
    out = segment_sum_batched_pallas(labels, cols, wts, nparts_pad=npad,
                                     block_b=_pick_block_rows(bpad),
                                     interpret=interpret)
    return out[:, :B, :nparts]


def connection_table(labels: jax.Array, cols: jax.Array, wts: jax.Array,
                     nparts: int, *, prefer: str = "auto") -> jax.Array:
    """``(B, nparts)`` table: ``conn[i, q] = Σ_k wts[i,k]·[labels[cols[i,k]]==q]``.

    Row-major ELL inputs ``cols``/``wts`` (B, w); padding entries point at
    any valid label slot with weight 0.  ``prefer``: "auto" (Pallas on
    TPU, jnp oracle elsewhere) | "pallas" | "ref".
    """
    B, w = cols.shape
    if B == 0 or w == 0:
        return jnp.zeros((B, nparts), jnp.float32)
    if prefer == "pallas" or (prefer == "auto" and on_tpu()):
        return _pallas_padded(labels, cols, wts, nparts=nparts,
                              interpret=not on_tpu())
    if prefer == "ref":
        return _ref_jit(labels, cols, wts, nparts=nparts)
    return _xla_loop(labels, cols, wts, nparts=nparts)


def connection_table_batched(labels: jax.Array, cols: jax.Array,
                             wts: jax.Array, nparts: int,
                             *, prefer: str = "auto") -> jax.Array:
    """Batched table build — ``labels`` (G, m), ``cols``/``wts`` (G, B, w)
    → (G, B, nparts) in ONE kernel launch (leading grid dim = shard
    group), the refinement sweep's per-collective compute step."""
    G, B, w = cols.shape
    if B == 0 or w == 0:
        return jnp.zeros((G, B, nparts), jnp.float32)
    if prefer == "pallas" or (prefer == "auto" and on_tpu()):
        return _pallas_batched_padded(labels, cols, wts, nparts=nparts,
                                      interpret=not on_tpu())
    if prefer == "ref":
        return _ref_batched_jit(labels, cols, wts, nparts=nparts)
    return _xla_loop_batched(labels, cols, wts, nparts=nparts)
