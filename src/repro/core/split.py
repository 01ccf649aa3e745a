"""Connected splits of one RSB tree node (host NumPy).

The plain spectral split sorts a node by its Fiedler vector and cuts at
the weighted ⌊p/2⌋ : ⌈p/2⌉ point.  On a mesh both halves come out
connected, and the engine keeps that split as it is.  On a skewed-degree
graph they do not: the Fiedler vector puts the low-degree periphery on
one side and the hubs that connect it on the other, so a half is made of
hundreds of isolated nodes, and no repair inside the balance corridor can
join them afterwards.  This module holds what the engine does instead.

* :func:`component_order` — the order of a *disconnected* node: by
  component (in the order of each component's lowest node), then by the
  Fiedler value of the component's own subgraph, a node alone by 0.
* :func:`part_plan` — a plan of the node's ``p`` parts, each grown as a
  connected region along the Fiedler order: the order is cut into ``p``
  chunks of equal weight, each part starts at its chunk's node of largest
  degree that is no earlier seed's neighbour, and the lightest part takes
  the next frontier node, nearest chunk first, then most edges into the
  part.  :func:`rebalance` then walks weight from heavy parts to light
  ones along chains of adjacent parts, one node per hop, taking only
  nodes whose loss keeps their part connected, until every part is
  within one node's weight of the mean.
* :func:`plan_halves` — the node's two halves as unions of planned parts,
  the ⌊p/2⌋ parts of lowest mean Fiedler value first, and each half's
  share of the plan: the half plans its own parts again along its own
  Fiedler vector (a better cut than its share), and falls back on its
  share where that fails (a hub with more pendant nodes than one part
  can hold).
"""

from __future__ import annotations

import heapq

import numpy as np

from repro.mesh.graphs import Graph, connected_labels


def components(g: Graph, mask: np.ndarray | None = None) -> tuple[np.ndarray, int]:
    """Component labels of ``g`` (of its subgraph on ``mask``: nodes off
    the mask get labels of their own, past those of the mask's), and the
    number of components on the mask."""
    rows, cols = g.rows, g.indices
    if mask is not None:
        keep = mask[rows] & mask[cols]
        rows, cols = rows[keep], cols[keep]
    lab = connected_labels(g.n, rows, cols)
    if mask is None:
        return lab, int(lab.max()) + 1 if g.n else 0
    return lab, int(np.unique(lab[mask]).size)


def component_order(comp: np.ndarray, values: np.ndarray,
                    ids: np.ndarray | None = None) -> np.ndarray:
    """The node order of a disconnected node: by component, components in
    the order of their lowest node (of the lowest of ``ids``, the nodes'
    numbers in the caller's graph, where given), then by ``values`` (each
    component's own Fiedler vector, 0 for a node alone)."""
    ids = np.arange(comp.size) if ids is None else np.asarray(ids, np.int64)
    first = np.full(int(comp.max()) + 1, np.iinfo(np.int64).max)
    np.minimum.at(first, comp, ids)
    return np.lexsort((values, first[comp]))


def sign_fixed(v: np.ndarray) -> np.ndarray:
    """``v`` with the sign that makes its entry of largest magnitude
    positive (an eigenvector's sign is arbitrary)."""
    v = np.asarray(v, dtype=np.float64)
    return v if v.size == 0 or v[np.argmax(np.abs(v))] >= 0 else -v


def articulation(g: Graph, mask: np.ndarray) -> np.ndarray:
    """Articulation points of ``g``'s subgraph on ``mask`` (iterative
    Tarjan): the nodes whose loss disconnects their component."""
    indptr, indices = g.indptr, g.indices
    disc = np.full(g.n, -1, dtype=np.int64)
    low = np.zeros(g.n, dtype=np.int64)
    art = np.zeros(g.n, dtype=bool)
    t = 0
    for root in np.flatnonzero(mask):
        if disc[root] >= 0:
            continue
        disc[root] = low[root] = t
        t += 1
        stack = [(int(root), -1, int(indptr[root]))]
        root_children = 0
        while stack:
            u, parent, i = stack[-1]
            end = indptr[u + 1]
            descended = False
            while i < end:
                v = int(indices[i])
                i += 1
                if not mask[v] or v == parent:
                    continue
                if disc[v] < 0:
                    stack[-1] = (u, parent, i)
                    disc[v] = low[v] = t
                    t += 1
                    stack.append((v, u, int(indptr[v])))
                    descended = True
                    break
                low[u] = min(low[u], disc[v])
            if descended:
                continue
            stack.pop()
            if stack:
                pu = stack[-1][0]
                low[pu] = min(low[pu], low[u])
                if pu == root:
                    root_children += 1
                elif low[u] >= disc[pu]:
                    art[pu] = True
        art[root] = root_children > 1
    return art


def grow_parts(g: Graph, w: np.ndarray, f: np.ndarray, p: int) -> np.ndarray:
    """``p`` connected parts of the connected graph ``g`` grown along the
    Fiedler vector ``f`` (module docstring): labels in ``[0, p)``."""
    n, indptr, indices = g.n, g.indptr, g.indices
    order = np.argsort(f, kind="stable")
    cw = np.cumsum(w[order])
    chunk = np.empty(n, dtype=np.int64)
    chunk[order] = np.minimum((cw - w[order] / 2) * p // cw[-1], p - 1)
    deg = np.diff(indptr)
    lab = np.full(n, -1, dtype=np.int64)
    part_w = np.zeros(p)
    conn = np.zeros((p, n), dtype=np.int64)   # edges from a node into a part
    heaps: list = [[] for _ in range(p)]

    def claim(j: int, v: int) -> None:
        lab[v] = j
        part_w[j] += w[v]
        nb = indices[indptr[v]:indptr[v + 1]]
        np.add.at(conn[j], nb, 1)
        for u in nb[lab[nb] < 0]:
            heapq.heappush(heaps[j], (abs(int(chunk[u]) - j),
                                      -int(conn[j, u]), int(u)))

    near_seed = np.zeros(n, dtype=bool)     # a seed or a seed's neighbour
    for j in range(p):
        members = order[chunk[order] == j]
        free = members[~near_seed[members]]
        pool = free if free.size else members[lab[members] < 0]
        if pool.size:
            v = int(pool[np.argmax(deg[pool])])
            claim(j, v)
            near_seed[v] = True
            near_seed[indices[indptr[v]:indptr[v + 1]]] = True
    left = n - int((lab >= 0).sum())
    while left:
        for j in np.argsort(part_w, kind="stable"):
            h = heaps[j]
            while h and (lab[h[0][2]] >= 0 or -h[0][1] != conn[j, h[0][2]]):
                heapq.heappop(h)
            if h:
                claim(int(j), heapq.heappop(h)[2])
                left -= 1
                break
        else:
            break   # no part can grow: g is not connected
    return lab


def rebalance(g: Graph, w: np.ndarray, lab: np.ndarray, p: int,
              *, max_moves: int | None = None) -> tuple[np.ndarray, int]:
    """Move weight from parts above the mean to parts below it, along
    chains of adjacent parts, until every part lies within one node's
    weight of the mean (or no chain is left).  Each hop of a chain moves
    one node whose loss keeps its part connected; where the giving part
    has none next to the taking one, the node that cuts off the least
    weight goes with what it cuts off, if that is no more than the chain's
    excess.  Returns the labels and the moves."""
    lab = lab.copy()
    rows, cols = g.rows, g.indices
    target = w.sum() / p
    slack = float(w.max())
    max_moves = 4 * g.n if max_moves is None else max_moves
    blocked: set = set()
    moves = 0
    while moves < max_moves:
        dev = np.bincount(lab, weights=w, minlength=p) - target
        if dev.max() < slack and -dev.min() < slack:
            break
        cross = lab[rows] != lab[cols]
        adj = np.zeros((p, p), dtype=bool)
        adj[lab[rows[cross]], lab[cols[cross]]] = True
        for a, b in blocked:
            adj[a, b] = False
        path = _chain(adj, dev, slack)
        if path is None:
            break
        excess = max(slack, min(dev[path[0]], -dev[path[-1]]))
        ok = True
        for a, b in reversed(list(zip(path[:-1], path[1:]))):
            in_a = lab == a
            into_b = np.bincount(rows[lab[cols] == b], minlength=g.n)
            into_a = np.bincount(rows[lab[cols] == a], minlength=g.n)
            touch = in_a & (into_b > 0)
            cand = np.flatnonzero(touch & ~articulation(g, in_a))
            if cand.size and in_a.sum() > 1:
                lab[cand[np.argmax(into_b[cand] - into_a[cand])]] = b
            else:
                moved = _lightest_cut_off(g, w, in_a, np.flatnonzero(touch))
                if moved is None or w[moved].sum() > excess:
                    blocked.add((a, b))
                    ok = False
                    break
                lab[moved] = b
            moves += 1
        if ok:
            blocked.clear()
    return lab, moves


def _chain(adj: np.ndarray, dev: np.ndarray, slack: float) -> list | None:
    """The shortest chain of adjacent parts (breadth first over ``adj``)
    from a part above the mean to one that can take a node: one at least
    ``slack`` below it, or below it by less where the giver is at least
    ``2·slack`` heavier.  Givers are tried heaviest first."""
    for src in np.argsort(-dev, kind="stable"):
        src = int(src)
        if dev[src] <= 0:
            return None
        prev = {src: -1}
        frontier = [src]
        while frontier:
            nxt = []
            for a in frontier:
                for b in np.flatnonzero(adj[a]):
                    b = int(b)
                    if b in prev:
                        continue
                    prev[b] = a
                    if dev[b] <= -slack or (dev[b] < 0 and
                                            dev[src] - dev[b] >= 2 * slack):
                        path = [b]
                        while prev[path[-1]] >= 0:
                            path.append(prev[path[-1]])
                        return path[::-1]
                    nxt.append(b)
            frontier = nxt
    return None


def _lightest_cut_off(g: Graph, w: np.ndarray, in_a: np.ndarray,
                      cand: np.ndarray) -> np.ndarray | None:
    """Among the nodes ``cand`` of the part ``in_a``, the one whose loss,
    together with the pieces of the part it cuts off, takes the least
    weight: that node set (None where every choice empties the part)."""
    best, best_w = None, np.inf
    for v in cand:
        rest = in_a.copy()
        rest[v] = False
        lab, _ = components(g, rest)
        keep_w = np.bincount(lab[rest], weights=w[rest])
        if keep_w.size == 0:
            continue
        take = in_a & (lab != np.argmax(keep_w))
        take_w = float(w[take].sum())
        if take_w < best_w:
            best, best_w = np.flatnonzero(take), take_w
    return best


def part_plan(g: Graph, w: np.ndarray, f: np.ndarray,
              p: int) -> tuple[np.ndarray, bool]:
    """Grown and rebalanced parts of a connected node, and whether every
    part is connected and within one node's weight of the mean."""
    lab, _ = rebalance(g, w, grow_parts(g, w, f, p), p)
    if (lab < 0).any():
        return lab, False
    dev = np.abs(np.bincount(lab, weights=w, minlength=p) - w.sum() / p)
    same = lab[g.rows] == lab[g.indices]
    comp = connected_labels(g.n, g.rows[same], g.indices[same])
    return lab, bool((dev < w.max()).all() and int(comp.max()) + 1 == p)


def plan_halves(plan: np.ndarray, f: np.ndarray,
                p: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The node's left half (a mask: the ⌊p/2⌋ planned parts of lowest
    mean ``f``) and each half's plan, its parts renumbered from 0."""
    k = p // 2
    mean = (np.bincount(plan, weights=f, minlength=p)
            / np.maximum(np.bincount(plan, minlength=p), 1))
    rank = np.argsort(mean, kind="stable")
    left_parts = np.zeros(p, dtype=bool)
    left_parts[rank[:k]] = True
    renum = np.empty(p, dtype=np.int64)
    renum[rank[:k]] = np.arange(k)
    renum[rank[k:]] = np.arange(p - k)
    left = left_parts[plan]
    sub = renum[plan]
    return left, sub[left], sub[~left]
