"""Work counts of one Lanczos restart and the table of peaks."""

import numpy as np
import pytest
import scipy.sparse as sp

import pb_reference
import pb_roofline


def test_restart_work_by_hand():
    # n = 4 rows, nnz = 6 stored entries, a window of 2 steps.
    # Steps: j = 0 and j = 1, each 2·6 + 15·4 = 72, plus 8·j·4.
    steps = 72 + (72 + 32)
    after = 2 * 2 * 4 + 2 * 6 + 14 * 4          # Ritz, residual, restart
    flops, nbytes = pb_roofline.restart_work(4, 6, 2)
    assert flops == steps + after == 260
    # Operator once (6 values + 6 column ids + 4 diagonal entries), the
    # start vector in, the Ritz and restart vectors out.
    assert nbytes == 6 * 8 + 4 * 4 + 3 * 4 * 4 == 112


def _path_and_star():
    """A path 0-1-2-3 joined to a star 4-(5, 6, 7): 7 edges, 14 stored
    entries, max degree 4."""
    edges = [(0, 1), (1, 2), (2, 3), (4, 5), (4, 6), (4, 7), (3, 4)]
    rows = [a for a, b in edges] + [b for a, b in edges]
    cols = [b for a, b in edges] + [a for a, b in edges]
    return pb_reference.DualGraph(adj=sp.csr_matrix(
        (np.ones(len(rows)), (rows, cols)), shape=(8, 8)))


def _problems(g, raw, nparts):
    """Per level, ``[(n, nnz), ...]`` of the nodes the reader counts."""
    out: dict = {}
    for level, _, idx in pb_reference.tree_nodes(np.array(raw), nparts):
        out.setdefault(level, []).append(
            (idx.size, pb_reference.subgraph(g, idx).nnz))
    return list(out.values())


def test_padding_adds_nothing():
    g = _path_and_star()
    # One problem of the real 8 rows and 14 entries: not the 16 slots of
    # a power-of-two pack, nor 8 rows × ELL width 4.
    assert _problems(g, [0, 0, 0, 0, 1, 1, 1, 1], 2) == [[(8, 14)]]
    # One level down each half keeps only its own entries; the cut edge
    # (3, 4) belongs to neither.
    assert _problems(g, [0, 0, 1, 1, 2, 2, 3, 3], 4) == [
        [(8, 14)], [(4, 6), (4, 6)]]
    f_real, b_real = pb_roofline.restart_work(8, 14, 20)
    f_pad, b_pad = pb_roofline.restart_work(16, 16 * 4, 20)
    assert f_real < f_pad and b_real < b_pad


def test_peaks_and_the_bound():
    pk = pb_roofline.peaks("TPU v5 lite")
    assert pk == {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        pb_roofline.peaks("TPU v99")
    # A 32^3 cube's level-0 restart is bound by memory.
    t, bound = pb_roofline.least_seconds(
        *pb_roofline.restart_work(32768, 770_000, 20), "TPU v5 lite")
    assert bound == "memory" and 5e-6 < t < 2e-5
