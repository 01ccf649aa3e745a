"""Distributed gather-scatter collectives (paper §5 under shard_map).

The paper's matrix-free Laplacian ``L x = d ⊙ x − A_w x`` distributes
verbatim: each shard broadcasts its elements' values to their vertices
(local ``P``), sums them into the *global* vertex-id space (local
``segment_sum``), a single ``psum`` over the mesh axis completes the
``Q Qᵀ`` exchange, and a local ``take`` copies the global sums back.  The
single-device reference is :mod:`repro.core.gather_scatter`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def dist_lap_apply_allreduce(gid: jax.Array, x_local: jax.Array,
                             deg: jax.Array, n_global: int,
                             axis_name: str) -> jax.Array:
    """One shard's slice of ``L x = d ⊙ x − A_w x`` (call inside shard_map).

    Parameters
    ----------
    gid : (E_loc, K) int — compacted global vertex ids of this shard's
        elements (a row-slice of :class:`repro.core.gather_scatter.GSHandle`
        ``.gid``).
    x_local : (E_loc,) — this shard's element values.
    deg : (E_loc,) — this shard's slice of ``L.degree_full`` (= A_w·1,
        self terms included; they cancel against ``d ⊙ x`` exactly as in
        the single-device path).
    n_global : total distinct global vertex ids.
    axis_name : mesh axis to ``psum`` over.
    """
    k = gid.shape[-1]
    flat_gid = gid.reshape(-1)
    # P: broadcast each element value to its K vertices (local).
    u = jnp.broadcast_to(x_local[..., None], x_local.shape + (k,)).reshape(-1)
    # Qᵀ (partial): sum this shard's vertex values into the global id space.
    partial = jax.ops.segment_sum(u, flat_gid, num_segments=n_global)
    # Complete Q Qᵀ with one all-reduce over the shards.
    full = jax.lax.psum(partial, axis_name)
    # Q + Pᵀ (local): copy global sums back, accumulate per element.
    aw_x = jnp.take(full, flat_gid).reshape(gid.shape).sum(axis=-1)
    return deg * x_local - aw_x
