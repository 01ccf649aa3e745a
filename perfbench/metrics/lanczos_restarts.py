"""Lanczos restarts per call: Σ ``LevelRecord.iterations`` over the levels
(each subproblem's restarts, summed)."""

import numpy as np


def read(run):
    if not all(c.levels for c in run.calls):
        return None
    return float(np.mean([sum(lv["iterations"] for lv in c.levels)
                          for c in run.calls]))
