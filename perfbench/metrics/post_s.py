"""Seconds per call in the post stages (repair and boundary refinement)."""

import numpy as np


def read(run):
    return float(np.mean([sum(s for k, _, s in c.stages if k == "post")
                          for c in run.calls]))
