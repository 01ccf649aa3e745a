"""Seconds per call in the bisect stage outside the level solves and
splits: the per-node RCB reorder, ``g.sub`` and bookkeeping."""

import numpy as np


def read(run):
    if not all(c.levels for c in run.calls):
        return None
    return float(np.mean([
        sum(s for k, _, s in c.stages if k == "bisect")
        - sum(lv["solve_seconds"] + lv["split_seconds"] for lv in c.levels)
        for c in run.calls]))
