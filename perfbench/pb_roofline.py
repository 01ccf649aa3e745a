"""Peaks of the chip and the least work of one Lanczos restart.

The least time the chip needs for a restart is the larger of its
operations over the peak rate and its bytes over the peak bandwidth.  The
counts take the real problem, not the program's layout: the real rows
``n`` and stored adjacency entries ``nnz`` of the subproblems solved
together (no padding slots, no ELL padding), the operator read once per
restart, and nothing for how the per-problem reductions are done.
"""

from __future__ import annotations

# device_kind -> peaks.  Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s (bf16) and 819 GB/s of HBM bandwidth per chip.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}

F32 = 4     # bytes of a float32 value
I32 = 4     # bytes of an int32 column index


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def restart_work(n: int, nnz: int, window: int) -> tuple[float, float]:
    """(flops, bytes) of one restart of a ``window``-step Lanczos with
    full reorthogonalisation (two passes, constants deflated) over ``n``
    real rows and ``nnz`` stored adjacency entries.

    Per step j (j earlier basis vectors): the matvec ``D x − A x``
    (2·nnz + 2n), the three-term recurrence and α (6n), two passes of
    reorthogonalisation against j vectors plus constant deflation
    (8jn + 4n), and β with the normalisation (3n).  After the window: the
    normalised Ritz vector (2·window·n + 3n), one residual matvec and its
    norm (2·nnz + 6n), and the deflated, normalised restart vector (5n).
    Bytes: the operator once (values, column indices, diagonal), the
    start vector in, the Ritz and restart vectors out.
    """
    m = window
    flops = (m * (2 * nnz + 15 * n) + 4 * m * (m - 1) * n
             + 2 * m * n + 2 * nnz + 14 * n)
    nbytes = nnz * (F32 + I32) + n * F32 + 3 * n * F32
    return float(flops), float(nbytes)


def least_seconds(flops: float, nbytes: float, device_kind: str
                  ) -> tuple[float, str]:
    """Least time and the bound that sets it ("compute" or "memory")."""
    pk = peaks(device_kind)
    tc, tm = flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")

