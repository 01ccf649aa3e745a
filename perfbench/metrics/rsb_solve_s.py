"""Seconds per call in the RSB level solves: Σ ``LevelRecord.solve_seconds``
(host warm start, packing and the device restarts)."""

import numpy as np


def read(run):
    if not all(c.levels for c in run.calls):
        return None
    return float(np.mean([sum(lv["solve_seconds"] for lv in c.levels)
                          for c in run.calls]))
