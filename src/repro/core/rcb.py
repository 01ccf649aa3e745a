"""Recursive Coordinate / Inertial Bisection (paper §3 + pre-partitioner §8).

RCB: find the longest coordinate axis, sort by that coordinate, split at the
weighted median, recurse.  RIB: same, but along the principal inertial axis
(covariance eigenvector), so cuts need not be axis-aligned.

Two uses in parRSB:
  * stand-alone geometric partitioners (quality baselines, Tables 1–4), and
  * the *pre-partitioner / ordering bootstrap*: `rcb_order` produces a full
    recursive ordering (down to singletons) that (a) makes element data
    locally contiguous before Lanczos/inverse iteration (paper: ≈2× speedup)
    and (b) seeds the AMG pairwise aggregation (paper §7: "We bootstrap the
    prolongation operator from an RCB ordering of the mesh elements").

Host-side NumPy: sorting-based, O(n log² n), exactly like the production
code's parallel sort usage.
"""

from __future__ import annotations

import numpy as np


def _principal_axis(coords: np.ndarray, weights: np.ndarray) -> np.ndarray:
    w = weights / weights.sum()
    mean = (coords * w[:, None]).sum(0)
    centered = coords - mean
    cov = (centered * w[:, None]).T @ centered
    eigval, eigvec = np.linalg.eigh(cov)
    return eigvec[:, -1]


def _axis_key(coords: np.ndarray, weights: np.ndarray, *, inertial: bool) -> np.ndarray:
    if inertial:
        return coords @ _principal_axis(coords, weights)
    extent = coords.max(0) - coords.min(0)
    return coords[:, int(np.argmax(extent))]


def _global_rescale(coords: np.ndarray) -> np.ndarray:
    """Paper §3: rescale ONCE so the global bounding box is isotropic
    (average element diameters match per axis).  Rescaling per-subset would
    equalize every subset's extents and degenerate RCB into slab cuts."""
    span = coords.max(0) - coords.min(0)
    span = np.where(span > 0, span, 1.0)
    return coords / span


def _weighted_split(keys: np.ndarray, weights: np.ndarray,
                    frac: float) -> tuple[np.ndarray, np.ndarray]:
    """Sort by key; split at the weighted `frac` quantile (indices)."""
    order = np.argsort(keys, kind="stable")
    cw = np.cumsum(weights[order])
    total = cw[-1]
    # smallest prefix with ≥ frac of the weight; ties keep element counts
    # within 1 for unit weights (paper Eq. 2.6)
    k = int(np.searchsorted(cw, frac * total, side="left")) + 1
    k = min(max(k, 1), keys.size - 1) if keys.size > 1 else 0
    return order[:k], order[k:]


def _segment_rescale(coords, starts, ends):
    """Rescale every segment by its own bounding-box span (zero spans → 1)."""
    full = ends > starts
    if not full.any():
        return coords
    lo = starts[full]
    span = np.maximum.reduceat(coords, lo) - np.minimum.reduceat(coords, lo)
    span = np.where(span > 0, span, 1.0)
    return coords / np.repeat(span, (ends - starts)[full], axis=0)


def _segment_keys(c, w, seg, offs, *, inertial):
    """Each segment's sort key: the coordinate along its longest axis (the
    first on ties), or its projection on the principal inertial axis."""
    if inertial:
        wn = w / np.add.reduceat(w, offs)[seg]
        mean = np.add.reduceat(c * wn[:, None], offs)
        cen = c - mean[seg]
        cov = np.add.reduceat((cen * wn[:, None])[:, :, None] * cen[:, None, :],
                              offs)
        axis = np.linalg.eigh(cov)[1][:, :, -1]
        return np.einsum("ij,ij->i", c, axis[seg])
    extent = np.maximum.reduceat(c, offs) - np.minimum.reduceat(c, offs)
    return c[np.arange(c.shape[0]), np.argmax(extent, axis=1)[seg]]


def rcb_order_segments(coords: np.ndarray, weights: np.ndarray | None,
                       bounds, *, inertial: bool = False,
                       rescale: bool = True) -> tuple[np.ndarray, int]:
    """Recursive-bisection orders of k segments at once, one tree depth
    at a time over every segment.

    Segment s is rows ``bounds[s]:bounds[s+1]``.  Each pass bisects every
    segment of two or more rows: stable sort on its key, then a split at
    the first prefix holding half its weight, left half first, written back
    in place.  The result is the order a left-first DFS of per-segment
    bisections gives, so a node of m elements takes ⌈log₂ m⌉ passes (unit
    weights) instead of m − 1 loop turns.

    Returns ``(order, passes)``: ``order`` permutes 0..n-1 and maps each
    segment's rows onto its own rows; ``passes`` counts the depth passes.
    """
    coords = np.asarray(coords, dtype=np.float64)
    n = coords.shape[0]
    w = np.ones(n) if weights is None else np.asarray(weights, dtype=np.float64)
    bounds = np.asarray(bounds, dtype=np.int64)
    starts, ends = bounds[:-1], bounds[1:]
    if rescale:
        coords = _segment_rescale(coords, starts, ends)
    order = np.arange(n, dtype=np.int64)
    passes = 0
    while True:
        live = ends - starts >= 2
        starts, ends = starts[live], ends[live]
        if not starts.size:
            return order, passes
        sizes = ends - starts
        offs = np.cumsum(sizes) - sizes
        seg = np.repeat(np.arange(sizes.size), sizes)
        local = np.arange(seg.size) - offs[seg]
        pos = starts[seg] + local
        cur = order[pos]
        key = _segment_keys(coords[cur], w[cur], seg, offs, inertial=inertial)
        cur = cur[np.lexsort((key, seg))]
        order[pos] = cur
        # Per-segment prefix sums in rows of their own, so each equals
        # np.cumsum of the segment alone whatever the weights.
        rows = np.zeros((sizes.size, int(sizes.max())))
        rows[seg, local] = w[cur]
        cw = np.cumsum(rows, axis=1)
        half = 0.5 * cw[np.arange(sizes.size), sizes - 1]
        # searchsorted(cw, half, "left") + 1, clamped to [1, size - 1]
        k = np.clip((cw < half[:, None]).sum(axis=1) + 1, 1, sizes - 1)
        mid = starts + k
        starts = np.stack([starts, mid], axis=1).ravel()
        ends = np.stack([mid, ends], axis=1).ravel()
        passes += 1


def rcb_order(coords: np.ndarray, weights: np.ndarray | None = None, *,
              inertial: bool = False, rescale: bool = True) -> np.ndarray:
    """Full recursive bisection ordering (permutation of 0..n-1).

    Contiguous chunks of the result are spatially compact at every dyadic
    scale — the property both the pre-partitioner and the AMG aggregation
    bootstrap rely on.
    """
    n = np.shape(coords)[0]
    return rcb_order_segments(coords, weights, [0, n], inertial=inertial,
                              rescale=rescale)[0]


def rib_order(coords: np.ndarray, weights: np.ndarray | None = None,
              *, rescale: bool = True) -> np.ndarray:
    return rcb_order(coords, weights, inertial=True, rescale=rescale)


def _parts_from_order(order: np.ndarray, weights: np.ndarray,
                      nparts: int) -> np.ndarray:
    """Split an ordering into `nparts` contiguous, weight-balanced chunks.

    Midpoint rule (cw − w/2) keeps unit-weight splits exact (≤1 element
    imbalance) instead of drifting on cumulative-sum ties."""
    w_sorted = weights[order]
    cw = np.cumsum(w_sorted)
    total = cw[-1]
    bounds = np.searchsorted(cw - w_sorted / 2,
                             total * np.arange(1, nparts) / nparts, side="left")
    parts = np.empty(order.size, dtype=np.int64)
    prev = 0
    for p, b in enumerate(np.r_[bounds, order.size]):
        parts[order[prev : b if p < nparts - 1 else order.size]] = p
        prev = b
    return parts


def rcb_parts(coords: np.ndarray, nparts: int,
              weights: np.ndarray | None = None, *, inertial: bool = False) -> np.ndarray:
    """RCB/RIB k-way partition via recursive proportional splits."""
    coords = _global_rescale(np.asarray(coords, dtype=np.float64))
    n = coords.shape[0]
    w = np.ones(n) if weights is None else np.asarray(weights, dtype=np.float64)
    parts = np.zeros(n, dtype=np.int64)

    def rec(idx: np.ndarray, p_lo: int, p_hi: int) -> None:
        np_parts = p_hi - p_lo
        if np_parts <= 1 or idx.size == 0:
            parts[idx] = p_lo
            return
        p_left = np_parts // 2
        keys = _axis_key(coords[idx], w[idx], inertial=inertial)
        lo, hi = _weighted_split(keys, w[idx], p_left / np_parts)
        rec(idx[lo], p_lo, p_lo + p_left)
        rec(idx[hi], p_lo + p_left, p_hi)

    rec(np.arange(n, dtype=np.int64), 0, nparts)
    return parts


def rib_parts(coords: np.ndarray, nparts: int,
              weights: np.ndarray | None = None) -> np.ndarray:
    return rcb_parts(coords, nparts, weights, inertial=True)
