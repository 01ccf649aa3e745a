"""Graph500 R-MAT: ``{"kind": "rmat", "scale": s, "edge_factor": f,
"abcd": [a, b, c, d], "graph_seed": g}``.

The Kronecker generator of the Graph 500 specification (graph500.org,
its reference ``kronecker_generator.m``): ``round(f · 2**s)``
edges over ``2**s`` vertices, each edge's row and column bits drawn level
by level with the quadrant probabilities ``a, b, c, d``, then the vertex
labels and the edge order permuted at random.  All of it is drawn from
``graph_seed``, so the graph is the same for every run seed.  The graph is
symmetrised and deduplicated, without self-loops, and **keeps only its
largest connected component** (R-MAT leaves many vertices isolated),
relabelled in the order of its vertices' labels.  Node weights are 1.
"""

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csgraph


def build(spec: dict):
    scale, a, b, c, d = spec["scale"], *spec["abcd"]
    if abs(a + b + c + d - 1.0) > 1e-9:
        raise ValueError(f"R-MAT probabilities {spec['abcd']} do not sum "
                         "to 1")
    n = 1 << scale
    m = int(round(spec["edge_factor"] * n))
    rng = np.random.default_rng(spec["graph_seed"])
    ij = np.zeros((2, m), dtype=np.int64)
    ab, c_norm, a_norm = a + b, c / (c + d), a / (a + b)
    for bit in range(scale):
        i_bit = rng.random(m) > ab
        j_bit = rng.random(m) > np.where(i_bit, c_norm, a_norm)
        ij += np.stack([i_bit, j_bit]).astype(np.int64) << bit
    ij = rng.permutation(n)[ij]
    ij = ij[:, rng.permutation(m)]
    src, dst = ij
    keep = src != dst
    adj = sp.coo_matrix((np.ones(int(keep.sum())), (src[keep], dst[keep])),
                        shape=(n, n)).tocsr()
    ncomp, comp = csgraph.connected_components(adj, directed=False)
    big = np.argmax(np.bincount(comp, minlength=ncomp))
    node = np.full(n, -1, dtype=np.int64)
    inside = comp == big
    node[inside] = np.arange(int(inside.sum()))
    src, dst = node[src[keep]], node[dst[keep]]
    both = (src >= 0) & (dst >= 0)
    k = int(inside.sum())
    return k, src[both], dst[both], np.ones(k), None
