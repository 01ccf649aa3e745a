"""A 2-D lattice, 4 neighbours a node, unit weights:
``{"kind": "grid", "dims": [nx, ny], "coords": true|false}``; with
``coords`` each node has its cell's centre in the unit square."""

import numpy as np


def build(spec: dict):
    nx, ny = spec["dims"]
    idx = np.arange(nx * ny).reshape(nx, ny)
    src = np.r_[idx[:-1, :].ravel(), idx[:, :-1].ravel()]
    dst = np.r_[idx[1:, :].ravel(), idx[:, 1:].ravel()]
    coords = None
    if spec["coords"]:
        ii, jj = np.meshgrid(np.arange(nx), np.arange(ny), indexing="ij")
        coords = (np.stack([ii.ravel(), jj.ravel()], axis=1) + 0.5) \
            / np.array([nx, ny], dtype=np.float64)
    return nx * ny, src, dst, np.ones(nx * ny), coords
