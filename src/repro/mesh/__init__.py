"""Mesh + graph substrate: hex meshes, dual graphs, graph generators."""

from repro.mesh.box import HexMesh, box_mesh
from repro.mesh.graphs import (
    Graph,
    build_csr,
    connected_components,
    connected_labels,
    csr_to_ell,
    dual_graph,
    dual_graph_from_incidence,
    extract_subgraphs,
    grid_graph_2d,
    grid_graph_3d,
    rmat_graph,
    stencil_graph_3d,
)
from repro.mesh.pebble import pebble_mesh
