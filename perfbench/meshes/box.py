"""Structured hex box, unit weights: ``{"kind": "box", "dims": [nx, ny, nz]}``."""

import numpy as np
import pb_mesh


def build(spec: dict):
    vert, coords = pb_mesh.grid(*spec["dims"])
    return vert, coords, np.ones(vert.shape[0])
