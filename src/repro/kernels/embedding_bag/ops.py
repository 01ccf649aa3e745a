"""Public dispatch: sorts segments if needed, pads dim to 128 lanes.

`prefer="auto"` runs the compiled Pallas kernel on TPU and the jnp
reference elsewhere; "pallas" forces the kernel (interpret off-TPU),
"ref" forces the oracle.  Same contract as `segment_sum.ops`."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import on_tpu
from repro.kernels.embedding_bag.kernel import embedding_bag_pallas
from repro.kernels.embedding_bag.ref import embedding_bag_ref



def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    segments: jax.Array,
    n_bags: int,
    *,
    weights: jax.Array | None = None,
    assume_sorted: bool = True,
    prefer: str = "auto",
) -> jax.Array:
    V, d = table.shape
    nnz = indices.shape[0]
    if weights is None:
        weights = jnp.ones((nnz,), table.dtype)
    if not assume_sorted:
        order = jnp.argsort(segments)
        indices, segments, weights = indices[order], segments[order], weights[order]
    if prefer == "ref" or (prefer == "auto" and not on_tpu()):
        return embedding_bag_ref(table, indices, segments, n_bags,
                                 weights=weights)
    d_pad = -(-d // 128) * 128
    tbl = jnp.pad(table, ((0, 0), (0, d_pad - d))) if d_pad != d else table
    out = embedding_bag_pallas(
        tbl, indices.astype(jnp.int32), segments.astype(jnp.int32), weights,
        n_bags=n_bags, interpret=not on_tpu(),
    )
    return out[:, :d]
