"""Hex meshes for the benchmark's inputs, made by the benchmark itself.

A mesh kind is a file ``perfbench/meshes/<kind>.py`` whose
``build(spec) -> (vert_gid, coords, weights)`` turns the ``mesh`` entry of
a configuration file into an ``(E, 8)`` corner-id table, element
centroids and element weights.  :func:`hex_mesh` numbers edges and faces
from the corners and hands the program its input type; :class:`MeshInput`
holds it for the harness.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np

import pb_reference

HERE = os.path.dirname(os.path.abspath(__file__))

# Local corner c = (dx, dy, dz) bits, x fastest.
CORNERS = np.array([(dx, dy, dz) for dz in (0, 1) for dy in (0, 1)
                    for dx in (0, 1)], dtype=np.int64)
HEX_EDGES = np.array([(0, 1), (2, 3), (4, 5), (6, 7), (0, 2), (1, 3),
                      (4, 6), (5, 7), (0, 4), (1, 5), (2, 6), (3, 7)])
HEX_FACES = np.array([(0, 2, 4, 6), (1, 3, 5, 7), (0, 1, 4, 5),
                      (2, 3, 6, 7), (0, 1, 2, 3), (4, 5, 6, 7)])


def load_kind(kind: str):
    path = os.path.join(HERE, "meshes", f"{kind}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no mesh kind {kind!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"perfbench_mesh_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def grid(nx: int, ny: int, nz: int) -> tuple[np.ndarray, np.ndarray]:
    """Corner ids and centroids of a structured nx × ny × nz unit box."""
    ii, jj, kk = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                             indexing="ij")
    ijk = np.stack([ii.ravel(), jj.ravel(), kk.ravel()], axis=1)
    corner = ijk[:, None, :] + CORNERS[None, :, :]
    vert = (corner[..., 0] * ((ny + 1) * (nz + 1))
            + corner[..., 1] * (nz + 1) + corner[..., 2])
    coords = (ijk + 0.5) / np.array([nx, ny, nz], dtype=np.float64)
    return vert.astype(np.int64), coords


def compact(ids: np.ndarray) -> tuple[np.ndarray, int]:
    uniq, inv = np.unique(ids, return_inverse=True)
    return inv.reshape(ids.shape).astype(np.int64), int(uniq.size)


def _number(keys: np.ndarray) -> tuple[np.ndarray, int]:
    uniq, inv = np.unique(keys, axis=0, return_inverse=True)
    return inv.ravel().astype(np.int64), int(uniq.shape[0])


def hex_mesh(vert_gid: np.ndarray, coords: np.ndarray, weights: np.ndarray):
    """The program's ``HexMesh`` with contiguous corner, edge and face ids
    (an edge is keyed by its sorted corner pair, a face by its sorted
    corner quadruple)."""
    from repro.mesh.box import HexMesh

    vert_gid, n_vert = compact(vert_gid)
    E = vert_gid.shape[0]
    edges = np.sort(vert_gid[:, HEX_EDGES], axis=-1).reshape(E * 12, 2)
    faces = np.sort(vert_gid[:, HEX_FACES], axis=-1).reshape(E * 6, 4)
    edge_gid, n_edge = _number(edges)
    face_gid, n_face = _number(faces)
    return HexMesh(vert_gid=vert_gid, edge_gid=edge_gid.reshape(E, 12),
                   face_gid=face_gid.reshape(E, 6),
                   coords=np.ascontiguousarray(coords, np.float64),
                   weights=np.ascontiguousarray(weights, np.float64),
                   n_vert=n_vert, n_edge=n_edge, n_face=n_face)



@dataclasses.dataclass(frozen=True)
class MeshInput:
    """A configuration's mesh in its base element order."""

    mesh: object                  # the program's HexMesh
    noun = "elements"

    @property
    def n(self) -> int:
        return self.mesh.nelems

    @property
    def weights(self) -> np.ndarray:
        return np.asarray(self.mesh.weights, np.float64)

    @property
    def coords(self) -> np.ndarray:
        return self.mesh.coords

    def call(self, perm: np.ndarray) -> tuple[object, dict]:
        """What one call hands ``pipe.run``: the mesh in the element order
        ``perm``."""
        return self.mesh.take(perm), {}

    def reference_graph(self) -> pb_reference.DualGraph:
        return pb_reference.dual_graph(self.mesh.vert_gid)
