"""Seconds per call in the guard's stages (``guard:validate``, which also
assembles the dual graph, and ``guard:finalize``)."""

import numpy as np


def read(run):
    return float(np.mean([sum(s for k, _, s in c.stages if k == "guard")
                          for c in run.calls]))
