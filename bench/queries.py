"""Query extraction: connected random-walk subgraphs (the paper's
protocol), copied from the program's ``random_walk_query``/``query_set``.

A walk from a random start collects ``n_vertices`` distinct vertices;
the query is the subgraph they induce, with their labels. So every
query has at least one embedding: the walk's own vertices.
"""
from __future__ import annotations

import numpy as np

from bench.graph import LabeledGraph


def random_walk_query(data: LabeledGraph, n_vertices: int, seed: int,
                      max_tries: int = 200) -> LabeledGraph:
    rng = np.random.default_rng(seed)
    for _ in range(max_tries):
        start = int(rng.integers(0, data.n))
        vset = {start}
        cur, steps = start, 0
        while len(vset) < n_vertices and steps < 50 * n_vertices:
            nbrs = data.neighbors(cur)
            steps += 1
            if len(nbrs) == 0:
                break
            cur = int(nbrs[rng.integers(0, len(nbrs))])
            vset.add(cur)
        if len(vset) == n_vertices:
            verts = sorted(vset)
            remap = {v: i for i, v in enumerate(verts)}
            edges = [(remap[a], remap[int(b)]) for a in verts
                     for b in data.neighbors(a) if int(b) in vset and a < b]
            labels = [int(data.labels[v]) for v in verts]
            return LabeledGraph.from_edges(n_vertices, edges, labels,
                                           data.n_labels)
    raise RuntimeError("could not extract a connected query")


def query_set(data: LabeledGraph, n_vertices: int, n_queries: int,
              seed: int) -> list[LabeledGraph]:
    return [random_walk_query(data, n_vertices, seed=seed * 100003 + i)
            for i in range(n_queries)]
