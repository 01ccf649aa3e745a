"""Undirected graphs for the benchmark's inputs, made by the benchmark itself.

A graph kind is a file ``perfbench/graphs/<kind>.py`` whose
``build(spec) -> (n, src, dst, node_weights, coords_or_None)`` turns the
``graph`` entry of a configuration file into an edge list over ``n``
nodes, node weights and, where the kind has them, node coordinates.
Like ``meshes/``, a kind imports nothing of the program.
:func:`build` symmetrises the edges, drops duplicates and self-loops, and
refuses a graph that is not connected: the reference's count of
disconnected parts is exact only for a connected input, so a kind that
wants a connected graph keeps its largest component and says so.  Every
edge weighs 1.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csgraph

import pb_reference

HERE = os.path.dirname(os.path.abspath(__file__))


def load_kind(kind: str):
    path = os.path.join(HERE, "graphs", f"{kind}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no graph kind {kind!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"perfbench_graph_{kind}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class GraphInput:
    """A configuration's graph in its base node order."""

    adj: sp.csr_matrix            # symmetric, unit weights, sorted rows
    weights: np.ndarray           # (n,) float64 node weights
    coords: np.ndarray | None     # (n, d) float64, or None
    noun = "nodes"

    @property
    def n(self) -> int:
        return self.adj.shape[0]

    def take(self, perm: np.ndarray) -> "GraphInput":
        """The same graph with node ``i`` the base graph's node
        ``perm[i]``."""
        perm = np.asarray(perm, np.int64)
        adj = self.adj[perm][:, perm].tocsr()
        adj.sort_indices()
        return GraphInput(
            adj=adj, weights=self.weights[perm],
            coords=None if self.coords is None else self.coords[perm])

    def reference_graph(self) -> pb_reference.DualGraph:
        return pb_reference.DualGraph(adj=self.adj)

    def call(self, perm: np.ndarray) -> tuple[object, dict]:
        """What one call hands ``pipe.run``, in the node order ``perm``:
        the program's ``Graph``, made here from the CSR arrays, and the
        node weights and coordinates."""
        from repro.mesh.graphs import Graph

        g = self.take(perm)
        kw = {"weights": g.weights}
        if g.coords is not None:
            kw["coords"] = g.coords
        return Graph(n=g.n, indptr=g.adj.indptr.astype(np.int64),
                     indices=g.adj.indices.astype(np.int64),
                     weights=g.adj.data.astype(np.float64)), kw


def build(spec: dict) -> GraphInput:
    """The graph a configuration's ``graph`` entry names; raises
    :class:`pb_harness.SetupError` where it is not connected."""
    from pb_harness import SetupError

    n, src, dst, weights, coords = load_kind(spec["kind"]).build(spec)
    adj = pb_reference.graph_from_edges(n, src, dst).adj
    ncomp, _ = csgraph.connected_components(adj, directed=False)
    if ncomp != 1:
        raise SetupError(f"graph kind {spec['kind']!r} gave {ncomp} "
                         "connected components; a graph input has one")
    return GraphInput(
        adj=adj, weights=np.ascontiguousarray(weights, np.float64),
        coords=None if coords is None
        else np.ascontiguousarray(coords, np.float64))
