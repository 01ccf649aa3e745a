"""Segmented, level-synchronous RCB ordering against the per-node DFS it
replaces: identical permutations over segment counts, tiny segments,
degenerate geometry and unit / integer / real weights."""

import numpy as np
import pytest

from repro.core import rcb_order, rib_order
from repro.core.rcb import rcb_order_segments
from repro.mesh import box_mesh


def _reference_order(coords, weights):
    """Per-node recursive coordinate bisection: a left-first DFS stack,
    one NumPy sort and split per tree node."""
    coords = np.asarray(coords, dtype=np.float64)
    span = coords.max(0) - coords.min(0)
    coords = coords / np.where(span > 0, span, 1.0)
    stack, ordered = [np.arange(coords.shape[0])], []
    while stack:
        cur = stack.pop()
        if cur.size <= 1:
            ordered.append(cur)
            continue
        c = coords[cur]
        keys = c[:, int(np.argmax(c.max(0) - c.min(0)))]
        order = np.argsort(keys, kind="stable")
        cw = np.cumsum(weights[cur][order])
        k = int(np.searchsorted(cw, 0.5 * cw[-1], side="left")) + 1
        k = min(max(k, 1), cur.size - 1)
        stack.append(cur[order[k:]])
        stack.append(cur[order[:k]])
    return np.concatenate(ordered)


def _reference_segments(coords, weights, bounds):
    return np.concatenate([
        a + _reference_order(coords[a:b], weights[a:b])
        for a, b in zip(bounds[:-1], bounds[1:])])


def _points(rng, n, geometry):
    c = rng.random((n, 3))
    if geometry == "plane":
        c[:, 2] = 0.5
    elif geometry == "line":
        c[:, 1:] = c[:, :1] * np.array([2.0, -1.0])
    elif geometry == "lattice":         # many tied keys
        c = np.floor(c * 4)
    return c


def _weights(rng, n, kind):
    if kind == "unit":
        return np.ones(n)
    if kind == "integer":
        return rng.integers(1, 6, n).astype(np.float64)
    return rng.random(n) + 1e-3


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("geometry", ["generic", "plane", "line", "lattice"])
@pytest.mark.parametrize("weights", ["unit", "integer", "real"])
@pytest.mark.parametrize("nseg", [1, 2, 7, 64])
def test_segments_match_reference(nseg, weights, geometry, seed):
    rng = np.random.default_rng([nseg, seed])
    sizes = rng.integers(1, 90, nseg)
    if nseg > 2:
        sizes[:2] = (1, 2)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n = int(bounds[-1])
    c, w = _points(rng, n, geometry), _weights(rng, n, weights)
    order, _ = rcb_order_segments(c, w, bounds)
    assert np.array_equal(order, _reference_segments(c, w, bounds))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_real_weight_prefix_sums_are_the_segments_own(seed):
    """A heavy first segment and decimal weights after it: a prefix sum
    taken across segments and rebased would round differently from the
    segment's own cumsum and move splits that sit on exact halves."""
    rng = np.random.default_rng(seed)
    sizes = np.r_[3, rng.integers(2, 40, 15)]
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    n = int(bounds[-1])
    c = _points(rng, n, "generic")
    w = rng.choice([0.1, 0.2, 0.3], n)
    w[:3] = 1e9
    order, _ = rcb_order_segments(c, w, bounds)
    assert np.array_equal(order, _reference_segments(c, w, bounds))


@pytest.mark.parametrize("sizes", [[1], [2], [1, 2, 1, 2, 1], [2] * 16,
                                   [1, 1, 3, 2, 1]])
def test_tiny_segments_match_reference(sizes):
    rng = np.random.default_rng(len(sizes))
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    c = _points(rng, int(bounds[-1]), "generic")
    w = np.ones(c.shape[0])
    order, passes = rcb_order_segments(c, w, bounds)
    assert np.array_equal(order, _reference_segments(c, w, bounds))
    assert passes == int(np.ceil(np.log2(max(sizes))))


@pytest.mark.parametrize("weights", ["unit", "integer", "real"])
def test_rcb_order_matches_reference_on_shuffled_cube(weights):
    rng = np.random.default_rng(7)
    c = box_mesh(8, 8, 8).coords[rng.permutation(512)]
    w = _weights(rng, 512, weights)
    assert np.array_equal(rcb_order(c, w), _reference_order(c, w))
    if weights == "unit":
        assert np.array_equal(rcb_order(c), _reference_order(c, w))


@pytest.mark.parametrize("sizes", [[512], [300, 257, 129], [64] * 8])
def test_passes_are_ceil_log2_of_largest_segment(sizes):
    rng = np.random.default_rng(0)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    c = _points(rng, int(bounds[-1]), "generic")
    _, passes = rcb_order_segments(c, None, bounds)
    assert passes == int(np.ceil(np.log2(max(sizes))))


@pytest.mark.parametrize("nseg", [1, 7])
def test_inertial_segments_match_per_segment_rib(nseg):
    """RIB through the segmented ordering: each segment's order is what
    rib_order gives on that segment alone."""
    rng = np.random.default_rng(nseg)
    sizes = rng.integers(2, 120, nseg)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    c = _points(rng, int(bounds[-1]), "generic")
    w = _weights(rng, c.shape[0], "real")
    order, _ = rcb_order_segments(c, w, bounds, inertial=True)
    per_segment = np.concatenate([
        a + rib_order(c[a:b], w[a:b])
        for a, b in zip(bounds[:-1], bounds[1:])])
    assert np.array_equal(order, per_segment)
    assert np.array_equal(np.sort(order), np.arange(c.shape[0]))
