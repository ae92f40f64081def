"""The benchmark's own labelled graph: CSR arrays built with numpy.

Data graphs and queries are generated as :class:`LabeledGraph`, so the
yardstick owns its data and its reference matcher; the harness turns
one into the program's graph type only when it hands it to the server.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class LabeledGraph:
    """Simple undirected vertex-labelled graph. ``indices`` holds both
    directions of every edge, sorted within each row."""
    n: int
    labels: np.ndarray      # int32 [n]
    indptr: np.ndarray      # int64 [n+1]
    indices: np.ndarray     # int32 [2E]
    n_labels: int

    @staticmethod
    def from_edges(n: int, edges, labels, n_labels: int) -> "LabeledGraph":
        """Drop self loops and repeated edges, keep both directions."""
        e = np.asarray(edges, np.int64).reshape(-1, 2)
        e = e[e[:, 0] != e[:, 1]]
        lo, hi = np.minimum(e[:, 0], e[:, 1]), np.maximum(e[:, 0], e[:, 1])
        key = np.unique(lo * n + hi)
        lo, hi = key // n, key % n
        src = np.concatenate([lo, hi])
        dst = np.concatenate([hi, lo])
        order = np.lexsort((dst, src))
        src, dst = src[order], dst[order]
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=n), out=indptr[1:])
        return LabeledGraph(n=int(n), labels=np.asarray(labels, np.int32),
                            indptr=indptr, indices=dst.astype(np.int32),
                            n_labels=int(n_labels))

    @property
    def n_edges(self) -> int:
        return int(self.indices.shape[0] // 2)

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)

    def neighbors(self, v: int) -> np.ndarray:
        return self.indices[self.indptr[v]:self.indptr[v + 1]]

    def edge_list(self) -> np.ndarray:
        """Every edge once, as ``[E, 2]`` with ``a < b``."""
        src = np.repeat(np.arange(self.n), self.degrees)
        keep = src < self.indices
        return np.stack([src[keep], self.indices[keep]], axis=1)


def zipf_labels(rng: np.random.Generator, n: int, n_labels: int,
                s: float = 1.1) -> np.ndarray:
    """A few frequent labels and a long tail; every label appears."""
    w = 1.0 / np.arange(1, n_labels + 1) ** s
    w /= w.sum()
    labels = rng.choice(n_labels, size=n, p=w)
    labels[:n_labels] = np.arange(n_labels)
    return labels.astype(np.int32)
