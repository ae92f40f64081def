"""Human-shaped data graph: Barabasi-Albert backbone plus uniform random
edges up to ``edge_target`` draws, Zipf labels. Repeated edges and
self loops are dropped, so the graph has a few edges fewer.

Copied from the program's ``human_like_graph``/``ba_labeled_graph``
(same random draws, so the same seed gives the same graph), with the
endpoint pool kept in a numpy buffer instead of a growing list.
"""
from __future__ import annotations

import numpy as np

from bench.graph import LabeledGraph, zipf_labels


def build(seed: int, vertices: int, edge_target: int, labels: int,
          attach: int) -> LabeledGraph:
    n, m = int(vertices), int(attach)
    rng = np.random.default_rng(seed)
    out: list[tuple[int, int]] = []
    pool = np.empty(n * 2 * m + m, np.int64)
    pool[:m] = np.arange(m)
    size = m
    for v in range(m, n):
        chosen = rng.choice(pool[:size], size=min(m, size), replace=False)
        for t in set(int(c) for c in chosen):
            out.append((v, t))
            pool[size] = t
            size += 1
        pool[size:size + m] = v
        size += m
    for _ in range(max(0, int(edge_target) - m * n)):
        a, b = rng.integers(0, n, size=2)
        if a != b:
            out.append((int(a), int(b)))
    lab = zipf_labels(rng, n, int(labels))
    return LabeledGraph.from_edges(n, out, lab, int(labels))
