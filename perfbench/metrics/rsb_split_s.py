"""Seconds per call in the RSB level splits: Σ ``LevelRecord.split_seconds``
(sort and split, child subgraph extraction)."""

import numpy as np


def read(run):
    if not all(c.levels for c in run.calls):
        return None
    return float(np.mean([sum(lv["split_seconds"] for lv in c.levels)
                          for c in run.calls]))
