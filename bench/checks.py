"""Answer checks: is a served answer the exact answer, up to the limit?

Copied in substance from the program's ``chip_smoke.check_answer`` and
``invalid_embedding``, vectorised, and compared with the benchmark's
own reference matcher (``bench/reference.py``) instead of the
program's oracle.

A status other than ``ok``/``limit`` is a failed request, not a wrong
answer; it is counted apart. For the rest:

* every row is a valid embedding (labels, edges, injective), and no
  row comes twice;
* ``limit``: exactly ``limit`` rows, and the reference finds at least
  that many;
* ``ok``: the rows are the reference's whole answer, which has fewer
  than ``limit`` embeddings.
"""
from __future__ import annotations

import numpy as np

from bench.graph import LabeledGraph

ANSWERED = ("ok", "limit")


class EdgeIndex:
    """Sorted keys ``lo * n + hi`` of every data edge, for vectorised
    edge tests."""

    def __init__(self, data: LabeledGraph):
        e = data.edge_list().astype(np.int64)
        self.n = data.n
        self.labels = data.labels
        self.keys = np.sort(e[:, 0] * data.n + e[:, 1])

    def has_edges(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        k = lo.astype(np.int64) * self.n + hi
        at = np.searchsorted(self.keys, k)
        at = np.minimum(at, len(self.keys) - 1)
        return (self.keys[at] == k) & (lo != hi)


def invalid_rows(query: LabeledGraph, index: EdgeIndex,
                 rows: np.ndarray) -> str | None:
    """Why some row of ``rows`` ([k, query.n], query vertex -> data
    vertex) is not an embedding, or None."""
    if rows.shape[0] == 0:
        return None
    if rows.ndim != 2 or rows.shape[1] != query.n:
        return f"rows of width {rows.shape[1:]} for a {query.n}-vertex query"
    if rows.min() < 0 or rows.max() >= index.n:
        return "row names a vertex outside the data graph"
    bad = np.flatnonzero((index.labels[rows] != query.labels).any(axis=1))
    if bad.size:
        return f"row {rows[bad[0]].tolist()}: a label differs"
    srt = np.sort(rows, axis=1)
    bad = np.flatnonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))
    if bad.size:
        return f"row {rows[bad[0]].tolist()}: mapping is not injective"
    for a, b in query.edge_list():
        ok = index.has_edges(rows[:, a], rows[:, b])
        if not ok.all():
            r = rows[np.flatnonzero(~ok)[0]].tolist()
            return f"row {r}: query edge ({a}, {b}) maps to a non-edge"
    return None


def check_answer(query: LabeledGraph, index: EdgeIndex, rows: np.ndarray,
                 status: str, reference: list[tuple[int, ...]] | None,
                 limit: int) -> str | None:
    """Why an answered request (status ``ok``/``limit``) disagrees with
    the exact answer, or None. ``reference`` is the reference matcher's
    answer at ``limit``; None only checks what the rows show alone."""
    rows = np.asarray(rows, np.int64).reshape(-1, query.n)
    why = invalid_rows(query, index, rows)
    if why is not None:
        return why
    if len(np.unique(rows, axis=0)) != len(rows):
        return "duplicate embedding rows"
    if status == "limit":
        if len(rows) != limit:
            return f"status limit with {len(rows)} rows, limit {limit}"
        if reference is not None and len(reference) < limit:
            return (f"status limit, but the reference finds only "
                    f"{len(reference)} embeddings")
        return None
    if len(rows) >= limit:
        return f"status ok with {len(rows)} rows at limit {limit}"
    if reference is None:
        return None
    if len(reference) >= limit:
        return (f"status ok with {len(rows)} rows; the reference finds "
                f"{limit} or more")
    want = set(reference)
    got = {tuple(int(v) for v in r) for r in rows}
    if got != want:
        return (f"embedding set differs from the reference's "
                f"({len(got)} rows vs {len(want)}; "
                f"{len(got - want)} extra, {len(want - got)} missing)")
    return None
