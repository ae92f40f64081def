"""Plain reference matcher: every embedding of a query, up to a limit.

An embedding maps each query vertex ``u`` to a data vertex ``row[u]``
with the same label, maps every query edge to a data edge, and is
injective (non-induced subgraph isomorphism). The search is plain
backtracking: candidates by label and degree, a connected matching
order that starts from the rarest vertex, and each next vertex drawn
from the data neighbours of one already-mapped query neighbour and
checked against the others. No pruning beyond that, and nothing shared
with the program.

``injective=False`` drops the injectivity rule: the control, a matcher
that breaks the guarantee the configurations state.
"""
from __future__ import annotations

import numpy as np

from bench.graph import LabeledGraph


class DataIndex:
    """Per-vertex neighbour sets and neighbours grouped by label."""

    def __init__(self, data: LabeledGraph):
        self.data = data
        self.labels = data.labels.tolist()
        self.degrees = data.degrees.tolist()
        self._sets: dict[int, frozenset] = {}
        self._by_label: dict[tuple[int, int], list[int]] = {}
        self._label_vertices = {
            int(lab): np.flatnonzero(data.labels == lab).tolist()
            for lab in np.unique(data.labels)}

    def nbr_set(self, v: int) -> frozenset:
        s = self._sets.get(v)
        if s is None:
            s = self._sets[v] = frozenset(self.data.neighbors(v).tolist())
        return s

    def nbrs_with_label(self, v: int, label: int) -> list[int]:
        key = (v, label)
        out = self._by_label.get(key)
        if out is None:
            nb = self.data.neighbors(v)
            out = self._by_label[key] = nb[
                self.data.labels[nb] == label].tolist()
        return out

    def vertices_with_label(self, label: int) -> list[int]:
        return self._label_vertices.get(int(label), [])


def matching_order(query: LabeledGraph, index: DataIndex) -> list[int]:
    """Rarest vertex first, then the unordered vertex with the most
    ordered neighbours (fewest candidates on ties)."""
    qdeg = query.degrees
    n_cand = [sum(1 for v in index.vertices_with_label(query.labels[u])
                  if index.degrees[v] >= qdeg[u]) for u in range(query.n)]
    order = [min(range(query.n), key=lambda u: (n_cand[u], -qdeg[u], u))]
    placed = {order[0]}
    while len(order) < query.n:
        def key(u):
            back = sum(1 for w in query.neighbors(u) if int(w) in placed)
            return (-back, n_cand[u], -qdeg[u], u)
        u = min((u for u in range(query.n) if u not in placed), key=key)
        order.append(u)
        placed.add(u)
    return order


def match(query: LabeledGraph, index: DataIndex, limit: int | None,
          injective: bool = True) -> list[tuple[int, ...]]:
    """Embeddings of ``query`` as tuples indexed by query vertex, at most
    ``limit`` of them (all where ``limit`` is None)."""
    n = query.n
    order = matching_order(query, index)
    pos = {u: i for i, u in enumerate(order)}
    qlab = [int(x) for x in query.labels]
    qdeg = [int(x) for x in query.degrees]
    # for each position: the earlier positions adjacent to it
    back = [sorted(pos[int(w)] for w in query.neighbors(u)
                   if pos[int(w)] < i) for i, u in enumerate(order)]
    deg = index.degrees
    mapped = [0] * n
    used: set[int] = set()
    out: list[tuple[int, ...]] = []

    def candidates(i: int):
        u = order[i]
        if not back[i]:
            return index.vertices_with_label(qlab[u])
        anchors = [mapped[p] for p in back[i]]
        anchor = min(anchors, key=lambda v: deg[v])
        cands = index.nbrs_with_label(anchor, qlab[u])
        others = [index.nbr_set(v) for v in anchors if v != anchor]
        if not others:
            return cands
        return [v for v in cands if all(v in s for s in others)]

    def extend(i: int) -> bool:
        """False once the limit is reached."""
        if i == n:
            row = [0] * n
            for p, u in enumerate(order):
                row[u] = mapped[p]
            out.append(tuple(row))
            return limit is None or len(out) < limit
        need = qdeg[order[i]]
        for v in candidates(i):
            if deg[v] < need or (injective and v in used):
                continue
            mapped[i] = v
            used.add(v)
            keep_going = extend(i + 1)
            used.discard(v)
            if not keep_going:
                return False
        return True

    if limit is None or limit > 0:
        extend(0)
    return out
