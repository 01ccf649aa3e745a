"""Device-resident sharded boundary refinement over the HaloPlan.

The host post chain (``repro.core.refine``) runs FM sweeps on the fully
assembled dual graph — the one stage that cannot scale past a single
host's memory.  This module ports the refinement *gain computation* onto
the existing :class:`~repro.dist.partition_aware.HaloPlan`: each shard
owns one part's node block, keeps only its ELL-packed frontier adjacency,
and the whole sweep loop runs under ``shard_map`` with exactly **one
all_gather of boundary labels per sweep**.

Protocol (per sweep, one fused collective)
------------------------------------------
1. **Exchange** — every shard packs one row buffer:
   ``[frontier labels | pending gains | pending targets | local part
   weights | local part counts]`` and a single tiled ``all_gather``
   replicates all P buffers everywhere.  Wire volume per sweep is
   ``P · (3·halo + 2·nparts)`` words — still ∝ the edge cut, and counted
   into the ``halo_words``/``halo_bytes`` counters.
2. **Gain table** — ONE batched segment-sum kernel launch
   (:func:`repro.kernels.segment_sum.ops.connection_table_batched`)
   computes every frontier node's (boundary × nparts) connection-weight
   table from the shard-local ELL adjacency, whose columns index the
   combined ``[local | gathered halo]`` label table.
3. **Conflict resolution** — *pending* proposals (computed from last
   sweep's state and shipped inside this sweep's gather, so every shard
   sees every boundary proposal) are resolved deterministically: a
   proposal survives only if it beats every proposing neighbor on the
   ``(gain, node id)`` priority (higher gain wins; ties go to the lower
   global node id).  Survivors form an independent set — no two adjacent
   nodes ever move in the same sweep, on any shard — so each applied
   move's *fresh* gain (recomputed from this sweep's table) is exact and
   the cut is monotonically non-increasing.
4. **Corridor** — part weights/counts are globally reduced from the same
   gather, and every shard replays the *identical* admission pass over
   all gathered proposals in ``(−gain, node id)`` order against the full
   corridor slack (node weights are static and replicated, source parts
   ride the gathered labels, so the pass is deterministic and identical
   everywhere).  A shard applies only ``admitted ∩ winners`` — a subset
   of a globally feasible move set — so P shards moving concurrently can
   never overflow the cap, dip under the floor, or empty a part.
   Proposals that lose the beat-test still hold their reservation for
   one sweep (conservative, never unsafe).
5. **Propose** — fresh positive-gain proposals for the *next* sweep are
   computed from the same table (first-max target, cap-feasible only)
   and ride the next gather.

A proposal is therefore applied one sweep after it is computed; the
fresh-gain re-check in step 3 discards any proposal staled by a remote
move in between.  The sweep loop, labels, and gain tables stay on device;
the host only sees per-sweep scalars (moves, realized gain, pending).

``refine_sharded_host`` is a NumPy mirror of the exact same arithmetic
(float32 where the device math is float32), used by the bit-parity tests:
on integer-weight meshes the device and host paths produce identical
labels.  The pipeline stages (``refine-sharded``, ``kway-sharded``) wrap
the sweep loop with the guard envelope — ``plan_halo_sharding`` already
self-heals ``halo_truncate`` chaos; an expired ``SolverGuard`` deadline,
or a result the stage rejects (invalid labels, a higher cut), degrades to
the host FM refiner (counted in ``guard_fallbacks``), while an exception
from the device path itself propagates — and close with a repair pass so
the zero-disconnected-parts invariant survives articulation moves.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core.refine import (
    PostStats,
    SweepRecord,
    balance_corridor,
    close_with_repair,
    edge_cut,
    refine_boundary,
)
from repro.dist.partition_aware import HaloPlan, plan_halo_sharding, scatter_features
from repro.guard.errors import RefineRejected
from repro.kernels.segment_sum.ops import connection_table_batched

EPS = 1e-6   # strict-positive-gain threshold (f32-safe)


# ---------------------------------------------------------------------------
# Frontier plan: the static per-shard arrays of the sweep loop
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class FrontierPlan:
    """Host-side static arrays for the sharded refinement sweep: the
    HaloPlan's export rows re-packed as per-shard ELL frontier adjacency
    plus the index maps conflict resolution needs."""

    plan: HaloPlan
    w: int                      # padded max frontier degree
    exp_slot: np.ndarray        # (P, halo) int32 local slot of export row
    exp_slot_sc: np.ndarray     # (P, halo) int32 scatter slot (pad→n_local)
    exp_mask: np.ndarray        # (P, halo) float32
    exp_w: np.ndarray           # (P, halo) float32 node weight
    exp_gid: np.ndarray         # (P, halo) int32 global node id (−1 pad)
    ell_cols: np.ndarray        # (P, halo, w) int32 combined-space neighbor
    ell_wts: np.ndarray         # (P, halo, w) float32 edge weight (0 pad)
    nbr_prow: np.ndarray        # (P, halo, w) int32 neighbor's gathered
                                #   proposal row in [0, P·halo) or −1
    node_w: np.ndarray          # (P, n_local) float32 node weights (0 pad)
    node_mask: np.ndarray       # (P, n_local) float32 1.0 on real slots

    @property
    def gather_row_words(self) -> int:
        """Words one shard contributes to the per-sweep all_gather."""
        return 3 * self.plan.halo + 2 * self.plan.n_shards


def build_frontier_plan(graph, parts, nparts: int, *,
                        weights: np.ndarray | None = None,
                        plan: HaloPlan | None = None) -> FrontierPlan:
    """Re-pack a :class:`HaloPlan`'s export rows as frontier ELL adjacency.

    Host-side NumPy, O(nnz log nnz) — the ``gs_setup`` analogue of the
    refinement sweep.  Every edge whose destination is an export row lands
    in that row's ELL slots, sorted by (shard, row, combined source) so
    the accumulation order is canonical on both device and host paths.
    """
    if plan is None:
        plan = plan_halo_sharding(graph, parts, nparts)
    n, nsh, halo, n_local = graph.n, plan.n_shards, plan.halo, plan.n_local
    w_node = (np.ones(n, np.float32) if weights is None
              else np.asarray(weights, np.float32))

    node_of = np.full((nsh, n_local), -1, np.int64)
    node_of[plan.shard_of, plan.slot_of] = np.arange(n, dtype=np.int64)
    erow_of_slot = np.full((nsh, n_local), -1, np.int64)
    msh, mro = np.nonzero(plan.export_mask > 0)
    erow_of_slot[msh, plan.export_idx[msh, mro]] = mro

    exp_gid = np.full((nsh, halo), -1, np.int32)
    exp_w = np.zeros((nsh, halo), np.float32)
    if msh.size:
        gids = node_of[msh, plan.export_idx[msh, mro]]
        exp_gid[msh, mro] = gids.astype(np.int32)
        exp_w[msh, mro] = w_node[gids]

    es, ep = np.nonzero(plan.edge_mask > 0)
    dst = plan.edge_dst[es, ep]
    src = plan.edge_src[es, ep]
    ew = plan.edge_weight[es, ep]
    row = erow_of_slot[es, dst]
    sel = row >= 0
    es, src, ew, row = es[sel], src[sel], ew[sel], row[sel]
    order = np.lexsort((src, row, es))
    es, src, ew, row = es[order], src[order], ew[order], row[order]

    key = es * np.int64(halo) + row
    cnt = np.bincount(key, minlength=nsh * halo) if key.size else \
        np.zeros(nsh * halo, np.int64)
    wmax = max(1, int(cnt.max())) if cnt.size else 1
    starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
    pos = np.arange(key.size, dtype=np.int64) - starts[key]

    ell_cols = np.zeros((nsh, halo, wmax), np.int32)
    ell_wts = np.zeros((nsh, halo, wmax), np.float32)
    nbr_prow = np.full((nsh, halo, wmax), -1, np.int32)
    if key.size:
        ell_cols[es, row, pos] = src.astype(np.int32)
        ell_wts[es, row, pos] = ew.astype(np.float32)
        local = src < n_local
        loc_row = erow_of_slot[es, np.clip(src, 0, n_local - 1)]
        prow = np.where(
            local,
            np.where(loc_row >= 0, es * np.int64(halo) + loc_row, -1),
            src - n_local,
        )
        nbr_prow[es, row, pos] = prow.astype(np.int32)

    return FrontierPlan(
        plan=plan, w=wmax,
        exp_slot=plan.export_idx.astype(np.int32),
        exp_slot_sc=np.where(plan.export_mask > 0, plan.export_idx,
                             n_local).astype(np.int32),
        exp_mask=plan.export_mask.astype(np.float32),
        exp_w=exp_w, exp_gid=exp_gid,
        ell_cols=ell_cols, ell_wts=ell_wts, nbr_prow=nbr_prow,
        node_w=scatter_features(plan, w_node).astype(np.float32),
        node_mask=scatter_features(plan, np.ones(n, np.float32)),
    )


# ---------------------------------------------------------------------------
# The device sweep (shard_map; ONE all_gather + ONE kernel launch per call)
# ---------------------------------------------------------------------------

def _global_admit(gain, tgt, src, w, valid, gid,
                  cap_room, floor_room, cnt_room):
    """The replicated corridor-admission pass: every shard runs this over
    ALL gathered proposals in (−gain, gid) order against the full global
    slack, producing the same admitted set everywhere without another
    collective.  A shard then applies ``admitted ∩ winners`` only."""
    M = gain.shape[0]
    nparts = cap_room.shape[0]
    order = jnp.argsort(gid)                   # ascending gid (stable)
    order = order[jnp.argsort(-gain[order])]   # stable ⇒ −gain, ties → gid

    def body(t, carry):
        add_u, rem_u, cnt_u, adm = carry
        i = order[t]
        ti = jnp.clip(tgt[i], 0)
        si = jnp.clip(src[i], 0)
        wi = w[i]
        fits = ((add_u[ti] + wi <= cap_room[ti])
                & (rem_u[si] + wi <= floor_room[si])
                & (cnt_u[si] + 1.0 <= cnt_room[si]))
        take = valid[i] & fits
        wadd = jnp.where(take, wi, 0.0)
        add_u = add_u.at[ti].add(wadd)
        rem_u = rem_u.at[si].add(wadd)
        cnt_u = cnt_u.at[si].add(jnp.where(take, 1.0, 0.0))
        return add_u, rem_u, cnt_u, adm.at[i].set(take)

    init = (jnp.zeros(nparts, jnp.float32), jnp.zeros(nparts, jnp.float32),
            jnp.zeros(nparts, jnp.float32), jnp.zeros(M, bool))
    *_, adm = jax.lax.fori_loop(0, M, body, init)
    return adm


def _sweep_body(gather, nparts, nsh, floor, cap, prefer,
                labels, pgain, ptgt, exp_slot, exp_slot_sc, exp_mask,
                exp_w, exp_gid, ell_cols, ell_wts, nbr_prow,
                node_w, node_mask, prow_gid, exp_w_flat):
    """One sweep on a group of G shards; ``gather`` is the collective
    (``all_gather`` under shard_map, identity when G == P)."""
    G, n_local = labels.shape
    halo = exp_slot.shape[1]
    floor = jnp.float32(floor)
    cap = jnp.float32(cap)

    # 1. pack + ONE all_gather of boundary labels (+ piggybacked proposals
    #    and part weight/count partials — same buffer, same collective).
    exp_lab = jnp.take_along_axis(labels, exp_slot, axis=1)      # (G, halo)
    pw_loc = jax.vmap(lambda l, v: jax.ops.segment_sum(
        v, l, num_segments=nparts))(labels, node_w)
    pn_loc = jax.vmap(lambda l, v: jax.ops.segment_sum(
        v, l, num_segments=nparts))(labels, node_mask)
    buf = jnp.concatenate([
        exp_lab.astype(jnp.float32), pgain, ptgt.astype(jnp.float32),
        pw_loc, pn_loc,
    ], axis=1)
    allbuf = gather(buf)                                         # (P, L)

    all_lab = allbuf[:, :halo].astype(jnp.int32).reshape(-1)     # (P·halo,)
    all_gain = allbuf[:, halo:2 * halo].reshape(-1)
    all_tgt = allbuf[:, 2 * halo:3 * halo].astype(jnp.int32).reshape(-1)
    pw = allbuf[:, 3 * halo:3 * halo + nparts].sum(axis=0)       # (nparts,)
    pn = allbuf[:, 3 * halo + nparts:].sum(axis=0)

    # 2. ONE batched segment-sum launch: the (boundary × nparts) table.
    combined = jnp.concatenate(
        [labels, jnp.broadcast_to(all_lab, (G, all_lab.size))], axis=1)
    conn = connection_table_batched(combined, ell_cols, ell_wts, nparts,
                                    prefer=prefer)               # (G,halo,np)
    own = exp_lab
    internal = jnp.take_along_axis(conn, own[..., None], axis=2)[..., 0]

    # 3. resolve pending proposals: (gain, node id) priority vs every
    #    proposing neighbor (all visible — they are all boundary rows).
    mask = exp_mask > 0
    valid = mask & (pgain > EPS) & (ptgt >= 0)
    safe = jnp.clip(nbr_prow, 0)
    nb_gain = jnp.where(nbr_prow >= 0, all_gain[safe], -jnp.inf)
    nb_tgt = jnp.where(nbr_prow >= 0, all_tgt[safe], -1)
    nb_gid = jnp.where(nbr_prow >= 0, prow_gid[safe], -1)
    nb_valid = (nbr_prow >= 0) & (nb_gain > EPS) & (nb_tgt >= 0)
    my_gain = pgain[..., None]
    my_gid = exp_gid[..., None]
    beaten = nb_valid & ((nb_gain > my_gain)
                         | ((nb_gain == my_gain) & (nb_gid < my_gid)))
    fresh = jnp.take_along_axis(
        conn, jnp.clip(ptgt, 0)[..., None], axis=2)[..., 0] - internal
    winner = valid & ~beaten.any(axis=-1) & (fresh > EPS)

    # 4. corridor on globally reduced part weights: the replicated global
    #    admission pass, then this device's shard rows of the result.
    cap_room = jnp.maximum(cap - pw, 0.0)
    floor_room = jnp.maximum(pw - floor, 0.0)
    cnt_room = jnp.floor(jnp.maximum(pn - 1.0, 0.0))
    prop_valid = (all_gain > EPS) & (all_tgt >= 0)
    adm_flat = _global_admit(all_gain, all_tgt, all_lab, exp_w_flat,
                             prop_valid, prow_gid,
                             cap_room, floor_room, cnt_room)
    d = jax.lax.axis_index("shards")
    my_adm = jax.lax.dynamic_slice_in_dim(
        adm_flat.reshape(-1, halo), d * G, G, axis=0)      # (G, halo)
    admitted = winner & my_adm
    new_val = jnp.where(admitted, ptgt, exp_lab)
    labels = jax.vmap(
        lambda l, s, v: l.at[s].set(v, mode="drop")
    )(labels, exp_slot_sc, new_val)

    # 5. fresh proposals for the next sweep (skip rows that just moved).
    iota = jnp.arange(nparts)
    conn2 = jnp.where(iota[None, None, :] == own[..., None], -jnp.inf, conn)
    conn2 = jnp.where(pw[None, None, :] + exp_w[..., None] <= cap,
                      conn2, -jnp.inf)
    best = conn2.argmax(axis=-1).astype(jnp.int32)
    bgain = jnp.take_along_axis(conn2, best[..., None], axis=2)[..., 0] \
        - internal
    src_ok = ((jnp.take(pw, own) - exp_w >= floor)
              & (jnp.take(pn, own) > 1.5))
    ok = mask & ~admitted & src_ok & (bgain > EPS) & jnp.isfinite(bgain)
    ngain = jnp.where(ok, bgain, -1.0).astype(jnp.float32)
    ntgt = jnp.where(ok, best, -1)

    moves = admitted.sum(axis=1).astype(jnp.float32)             # (G,)
    gained = jnp.where(admitted, fresh, 0.0).sum(axis=1)
    pending = ok.sum(axis=1).astype(jnp.float32)
    return labels, ngain, ntgt, moves, gained, pending


@functools.lru_cache(maxsize=16)
def _device_step(fp: FrontierPlan, nparts: int, floor: float, cap: float,
                 n_dev: int):
    """Jitted per-(plan, corridor, mesh) sweep step + device constants.
    ``n_dev`` devices each own ``P / n_dev`` shards; with one device the
    all_gather degenerates to the identity but the code path is the same."""
    mesh = jax.make_mesh((n_dev,), ("shards",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    prefer = "auto"

    def gather(buf):
        return jax.lax.all_gather(buf, "shards", axis=0, tiled=True)

    body = functools.partial(_sweep_body, gather, nparts, fp.plan.n_shards,
                             floor, cap, prefer)
    spec = P("shards")
    rep = P()
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec,) * 13 + (rep, rep),
        out_specs=(spec,) * 6,
        check_vma=False,
    ))
    # Each constant goes straight to its devices with the sharding the
    # step expects: staged through the default device first, device 0
    # would keep an unsharded copy of the whole plan.
    shard = NamedSharding(mesh, spec)
    whole = NamedSharding(mesh, rep)
    consts = tuple(jax.device_put(a, shard) for a in (
        fp.exp_slot, fp.exp_slot_sc, fp.exp_mask, fp.exp_w, fp.exp_gid,
        fp.ell_cols, fp.ell_wts, fp.nbr_prow, fp.node_w, fp.node_mask,
    )) + tuple(jax.device_put(a, whole) for a in (
        fp.exp_gid.reshape(-1), fp.exp_w.reshape(-1)))
    return fn, consts, mesh


def _pick_devices(n_shards: int, max_devices: int | None = None) -> int:
    """Largest divisor of ``n_shards`` that fits the local device count —
    each device then owns a contiguous group of shards."""
    avail = len(jax.devices()) if max_devices is None \
        else min(max_devices, len(jax.devices()))
    for d in range(min(n_shards, avail), 0, -1):
        if n_shards % d == 0:
            return d
    return 1


# ---------------------------------------------------------------------------
# Sweep runners (device + NumPy mirror)
# ---------------------------------------------------------------------------

def run_sharded_sweeps(fp: FrontierPlan, parts: np.ndarray, nparts: int, *,
                       sweeps: int = 4, corridor: tuple,
                       backend: str = "auto",
                       max_devices: int | None = None):
    """Run the sharded sweep loop; returns ``(labels, records, info)``.

    ``sweeps`` counts gather rounds (the first round only seeds proposals,
    so moves land from round 2 on).  ``backend``: "auto"/"device" runs the
    shard_map path across ``_pick_devices`` devices; "host" runs the NumPy
    mirror.  Per sweep the loop emits ``halo_words``/``halo_bytes`` wire
    counters plus ``sharded_gathers``/``sharded_sweeps`` (always equal —
    the one-collective-per-sweep contract the smoke gate asserts).
    """
    plan = fp.plan
    parts = np.asarray(parts, dtype=np.int64)
    cut0 = _plan_cut(fp, parts)
    if plan.halo == 0 or sweeps <= 0:       # no cross-shard frontier
        return parts.copy(), [], {"moves": 0, "gathers": 0, "cut": cut0}
    if backend == "host":
        return refine_sharded_host(fp, parts, nparts, sweeps=sweeps,
                                   corridor=corridor)
    floor, cap = float(corridor[0]), float(corridor[1])
    n_dev = _pick_devices(plan.n_shards, max_devices)
    fn, consts, mesh = _device_step(fp, nparts, floor, cap, n_dev)

    shard = NamedSharding(mesh, P("shards"))
    labels = jax.device_put(scatter_features(plan, parts).astype(np.int32),
                            shard)
    pgain = jax.device_put(
        np.full((plan.n_shards, plan.halo), -1.0, np.float32), shard)
    ptgt = jax.device_put(
        np.full((plan.n_shards, plan.halo), -1, np.int32), shard)

    records, total_moves, gathers, cut = [], 0, 0, cut0
    words = plan.n_shards * fp.gather_row_words
    for s in range(sweeps):
        with obs.timed(f"sweep:{s}"):
            labels, pgain, ptgt, mv, gn, pend = fn(labels, pgain, ptgt,
                                                   *consts)
            mv = int(np.asarray(mv).sum())
            gn = float(np.asarray(gn).sum())
            pend = int(np.asarray(pend).sum())
            gathers += 1
            obs.counter_add("halo_words", float(words))
            obs.counter_add("halo_bytes", 4.0 * words)
            obs.counter_add("sharded_gathers", 1)
            obs.counter_add("sharded_sweeps", 1)
            obs.counter_add("sharded_moves", mv)
        records.append(SweepRecord(sweep=s, moves=mv, cut_before=cut,
                                   cut_after=cut - gn))
        cut -= gn
        total_moves += mv
        if mv == 0 and pend == 0:
            break

    blocks = np.asarray(labels, dtype=np.int64)
    out = blocks[plan.shard_of, plan.slot_of]
    return out, records, {"moves": total_moves, "gathers": gathers,
                          "cut": cut}


def _plan_cut(fp: FrontierPlan, parts: np.ndarray) -> float:
    """Edge cut from the plan's own edge lists (no global graph needed)."""
    plan = fp.plan
    sel = plan.edge_mask > 0
    es, ep = np.nonzero(sel)
    dst_g = np.full((plan.n_shards, plan.n_local), 0, np.int64)
    dst_g[plan.shard_of, plan.slot_of] = parts
    combined = _combined_labels_host(fp, parts)
    pd = dst_g[es, plan.edge_dst[es, ep]]
    ps = combined[es, plan.edge_src[es, ep]]
    return float(plan.edge_weight[es, ep][pd != ps].sum() / 2.0)


def _combined_labels_host(fp: FrontierPlan, parts: np.ndarray) -> np.ndarray:
    """(P, n_local + P·halo) combined label table, NumPy."""
    plan = fp.plan
    blocks = scatter_features(plan, parts).astype(np.int64)
    msh, mro = np.nonzero(fp.exp_mask > 0)
    halo_lab = np.zeros(plan.n_shards * plan.halo, np.int64)
    halo_lab[msh * plan.halo + mro] = blocks[msh, fp.exp_slot[msh, mro]]
    return np.concatenate(
        [blocks, np.broadcast_to(halo_lab, (plan.n_shards, halo_lab.size))],
        axis=1)


def refine_sharded_host(fp: FrontierPlan, parts: np.ndarray, nparts: int, *,
                        sweeps: int = 4, corridor: tuple):
    """NumPy mirror of the device sweep — same protocol, same float32
    arithmetic, same tie-breaks — for bit-parity tests and as the
    reference the device path is audited against."""
    plan = fp.plan
    nsh, halo, n_local = plan.n_shards, plan.halo, plan.n_local
    floor = np.float32(corridor[0])
    cap = np.float32(corridor[1])

    labels = scatter_features(plan, np.asarray(parts, np.int64))
    pgain = np.full((nsh, halo), -1.0, np.float32)
    ptgt = np.full((nsh, halo), -1, np.int32)
    mask = fp.exp_mask > 0
    cut = _plan_cut(fp, np.asarray(parts, np.int64))

    records, total_moves, gathers = [], 0, 0
    for s in range(sweeps):
        # 1. "gather": labels + proposals + part weight/count partials.
        exp_lab = np.take_along_axis(labels, fp.exp_slot.astype(np.int64),
                                     axis=1)
        pw = np.zeros(nparts, np.float32)
        pn = np.zeros(nparts, np.float32)
        for g in range(nsh):   # f32 accumulation, shard-major like device
            np.add.at(pw, labels[g], fp.node_w[g])
            np.add.at(pn, labels[g], fp.node_mask[g])
        all_lab = np.where(mask, exp_lab, 0).reshape(-1)
        all_gain = pgain.reshape(-1)
        all_tgt = ptgt.reshape(-1)
        gathers += 1

        # 2. connection table (f32; canonical ELL slot order).
        combined = np.concatenate(
            [labels, np.broadcast_to(all_lab, (nsh, all_lab.size))], axis=1)
        conn = np.zeros((nsh, halo, nparts), np.float32)
        gi, ri, ki = np.nonzero(fp.ell_wts > 0)
        lab_n = combined[gi, fp.ell_cols[gi, ri, ki]]
        np.add.at(conn, (gi, ri, lab_n), fp.ell_wts[gi, ri, ki])
        own = exp_lab
        ar_g, ar_r = np.meshgrid(np.arange(nsh), np.arange(halo),
                                 indexing="ij")
        internal = conn[ar_g, ar_r, np.where(mask, own, 0)]

        # 3. resolve pending proposals.
        valid = mask & (pgain > EPS) & (ptgt >= 0)
        safe = np.clip(fp.nbr_prow, 0, None)
        has = fp.nbr_prow >= 0
        nb_gain = np.where(has, all_gain[safe], -np.inf)
        nb_tgt = np.where(has, all_tgt[safe], -1)
        nb_gid = np.where(has, fp.exp_gid.reshape(-1)[safe], -1)
        nb_valid = has & (nb_gain > EPS) & (nb_tgt >= 0)
        beaten = (nb_valid & ((nb_gain > pgain[..., None])
                              | ((nb_gain == pgain[..., None])
                                 & (nb_gid < fp.exp_gid[..., None]))))
        fresh = conn[ar_g, ar_r, np.clip(ptgt, 0, None)] - internal
        winner = valid & ~beaten.any(axis=-1) & (fresh > EPS)

        # 4. the replicated global corridor-admission pass (identical to
        #    every shard's device-side replay), then admitted ∩ winners.
        cap_room = np.maximum(cap - pw, 0.0).astype(np.float32)
        floor_room = np.maximum(pw - floor, 0.0).astype(np.float32)
        cnt_room = np.floor(np.maximum(pn - 1.0, 0.0)).astype(np.float32)
        prop_valid = (all_gain > EPS) & (all_tgt >= 0)
        all_w = fp.exp_w.reshape(-1)
        gid_flat = fp.exp_gid.reshape(-1)
        order = np.argsort(gid_flat, kind="stable")
        order = order[np.argsort(-all_gain[order], kind="stable")]
        add_u = np.zeros(nparts, np.float32)
        rem_u = np.zeros(nparts, np.float32)
        cnt_u = np.zeros(nparts, np.float32)
        adm_flat = np.zeros(nsh * halo, bool)
        for i in order:
            if not prop_valid[i]:
                continue
            ti, si = int(all_tgt[i]), int(all_lab[i])
            wi = all_w[i]
            if (add_u[ti] + wi <= cap_room[ti]
                    and rem_u[si] + wi <= floor_room[si]
                    and cnt_u[si] + 1.0 <= cnt_room[si]):
                add_u[ti] += wi
                rem_u[si] += wi
                cnt_u[si] += 1.0
                adm_flat[i] = True
        admitted = winner & adm_flat.reshape(nsh, halo)
        moves = int(admitted.sum())
        gained = np.float32(0.0)
        for g, i in zip(*np.nonzero(admitted)):
            labels[g, fp.exp_slot[g, i]] = ptgt[g, i]
            gained += fresh[g, i]

        # 5. fresh proposals for the next sweep.
        conn2 = conn.copy()
        conn2[ar_g, ar_r, np.where(mask, own, 0)] = -np.inf
        tgt_fits = pw[None, None, :] + fp.exp_w[..., None] <= cap
        conn2 = np.where(tgt_fits, conn2, -np.inf)
        best = conn2.argmax(axis=-1).astype(np.int32)
        bgain = conn2[ar_g, ar_r, best] - internal
        src_ok = (pw[np.where(mask, own, 0)] - fp.exp_w >= floor) \
            & (pn[np.where(mask, own, 0)] > 1.5)
        ok = mask & ~admitted & src_ok & (bgain > EPS) & np.isfinite(bgain)
        pgain = np.where(ok, bgain, -1.0).astype(np.float32)
        ptgt = np.where(ok, best, -1).astype(np.int32)

        records.append(SweepRecord(sweep=s, moves=moves, cut_before=cut,
                                   cut_after=cut - float(gained)))
        cut -= float(gained)
        total_moves += moves
        if moves == 0 and not ok.any():
            break

    out = labels[plan.shard_of, plan.slot_of]
    return out, records, {"moves": total_moves, "gathers": gathers,
                          "cut": cut}


# ---------------------------------------------------------------------------
# Pipeline post stages
# ---------------------------------------------------------------------------

def _sharded_pass(graph, parts, nparts, *, weights, sweeps, corridor,
                  backend, guard, stats: PostStats):
    """Shared core of the two stages: guard envelope → sharded sweeps →
    fall back to the host FM refiner when the guard rejects the result.
    Exceptions from the device path itself (compile, lowering, runtime,
    out of memory) propagate: they are faults, not degraded results."""
    parts = np.asarray(parts, dtype=np.int64)
    if guard is not None and getattr(guard, "expired", lambda: False)():
        # guard.expired() itself emits guard_deadline_expired on first trip.
        stats.stages.append("host-fallback")
        return refine_boundary(graph, parts, nparts, weights=weights,
                               sweeps=sweeps, corridor=corridor)[0], False
    try:
        fp = build_frontier_plan(graph, parts, nparts, weights=weights)
        out, records, info = run_sharded_sweeps(
            fp, parts, nparts, sweeps=sweeps, corridor=corridor,
            backend=backend)
        out = np.asarray(out, dtype=np.int64)
        if (out.shape != parts.shape or out.min() < 0
                or out.max() >= nparts):
            raise RefineRejected("sharded refinement produced invalid labels")
        cut_now = edge_cut(graph, out)
        if cut_now > stats.cut_before + 1e-6:
            raise RefineRejected(
                f"sharded refinement increased the cut "
                f"({stats.cut_before} -> {cut_now})")
        stats.sweeps.extend(records)
        stats.moves_applied += info["moves"]
        return out, True
    except RefineRejected:
        # Guard escalation: the sweep result failed the stage's checks —
        # degrade to the host FM refiner rather than ship it.
        obs.counter_add("guard_fallbacks", 1)
        stats.stages.append("host-fallback")
        out, fstats = refine_boundary(graph, parts, nparts, weights=weights,
                                      sweeps=sweeps, corridor=corridor)
        stats.sweeps.extend(fstats.sweeps)
        stats.moves_applied += fstats.moves_applied
        return out, False


def refine_sharded_stage(
    graph,
    parts: np.ndarray,
    nparts: int,
    *,
    weights: np.ndarray | None = None,
    sweeps: int = 4,
    balance_tol: float = 0.05,
    corridor: tuple | None = None,
    backend: str = "auto",
    guard=None,
) -> tuple[np.ndarray, PostStats]:
    """The pipeline's "refine-sharded" stage: device-resident frontier FM
    sweeps (one boundary-label all_gather per sweep) + a closing repair
    pass.  Cut-non-increasing under ONE corridor, like the host stage."""
    if corridor is None:
        corridor = balance_corridor(parts, nparts, weights, balance_tol)
    stats = PostStats(stages=["refine-sharded"], corridor=tuple(corridor),
                      cut_before=edge_cut(graph, parts))
    with obs.timed("sharded_sweeps_total") as t:
        parts, _ok = _sharded_pass(graph, parts, nparts, weights=weights,
                                   sweeps=sweeps, corridor=corridor,
                                   backend=backend, guard=guard,
                                   stats=stats)
    stats.seconds = t.seconds
    obs.counter_add("refine_moves", stats.moves_applied)
    return close_with_repair(graph, parts, nparts, stats, weights=weights,
                             balance_tol=balance_tol, corridor=corridor)


def kway_sharded_stage(
    graph,
    parts: np.ndarray,
    nparts: int,
    *,
    weights: np.ndarray | None = None,
    sweeps: int = 4,
    passes: int = 2,
    balance_tol: float = 0.05,
    corridor: tuple | None = None,
    backend: str = "auto",
    guard=None,
) -> tuple[np.ndarray, PostStats]:
    """The "kway-sharded" stage: sharded frontier sweeps for the bulk of
    the gain, then a host boundary-restricted hill-climbing k-way polish
    (the part that needs global move ordering), then the closing repair."""
    from repro.core.kway import kway_fm_boundary

    if corridor is None:
        corridor = balance_corridor(parts, nparts, weights, balance_tol)
    stats = PostStats(stages=["kway-sharded"], corridor=tuple(corridor),
                      cut_before=edge_cut(graph, parts))
    with obs.timed("sharded_sweeps_total") as t:
        parts, _ok = _sharded_pass(graph, parts, nparts, weights=weights,
                                   sweeps=sweeps, corridor=corridor,
                                   backend=backend, guard=guard,
                                   stats=stats)
    stats.seconds = t.seconds
    parts, kstats = kway_fm_boundary(graph, parts, nparts, weights=weights,
                                     passes=passes, corridor=corridor)
    stats.kway = kstats.kway
    stats.moves_applied += kstats.moves_applied
    stats.seconds += kstats.seconds
    obs.counter_add("refine_moves", stats.moves_applied)
    return close_with_repair(graph, parts, nparts, stats, weights=weights,
                             balance_tol=balance_tol, corridor=corridor)
