"""Candidate filtering: the CFL sweep over the candidates' own CSR rows
gives the same lists as the sweep over the whole data graph, the data
graph's neighbour-label table is built once, and the filters are sound."""
import functools

import numpy as np
import pytest

from repro.core.backtrack import backtrack_deadend
from repro.core.candidates import (build_candidates, cfl_refine, ldf_filter,
                                   nlf_filter)
from repro.core.graph import Graph
from repro.data.graph_gen import (er_labeled_graph, human_like_graph,
                                  query_set, random_walk_query)


# ---- reference: the CFL sweep over the whole data CSR --------------------
def _ref_refine_once(query: Graph, data: Graph,
                     cand_masks: list[np.ndarray]) -> bool:
    """One sweep of edge-consistency refinement (AC-ish / CFL passes).

    cand_masks[u] is a boolean mask over data vertices. A candidate v of u
    survives only if, for every query neighbor u', v has at least one data
    neighbor that is a candidate of u'. Returns True if anything changed.
    """
    changed = False
    nnz = data.indices.size
    # one reduceat over the data CSR per (u, u') pair instead of a
    # Python loop over candidates — the per-vertex generator dominated
    # submit latency on the serving path. The segment sum counts a
    # vertex's neighbors that are candidates of u'; empty rows read a
    # garbage segment and are masked via ``nonempty``.
    starts = np.minimum(data.indptr[:-1], max(nnz - 1, 0))
    nonempty = (data.indptr[1:] - data.indptr[:-1]) > 0
    for u in range(query.n):
        mask_u = cand_masks[u]
        if not mask_u.any():
            continue
        keep = mask_u.copy()
        for uq in query.neighbors(u):
            if nnz == 0:
                keep[:] = False
                break
            m_other = cand_masks[int(uq)]
            # v survives iff any neighbor of v is in m_other
            hit = np.add.reduceat(m_other[data.indices], starts) > 0
            keep &= nonempty & hit
            if not keep.any():
                break
        if not np.array_equal(keep, mask_u):
            changed = True
            cand_masks[u] = keep
    return changed


def _ref_cfl_refine(query: Graph, data: Graph, cand: list[np.ndarray],
                    max_rounds: int = 3) -> list[np.ndarray]:
    """Fixpoint edge-consistency refinement (bounded rounds).

    Strictly sound: only candidates provably absent from every embedding
    are removed (they lack an adjacent candidate for some query neighbor).
    """
    masks = []
    for u in range(query.n):
        m = np.zeros(data.n, dtype=bool)
        m[cand[u]] = True
        masks.append(m)
    for _ in range(max_rounds):
        if not _ref_refine_once(query, data, masks):
            break
    return [np.nonzero(m)[0].astype(np.int32) for m in masks]


def _ref(query: Graph, data: Graph, cand: list[np.ndarray],
         max_rounds: int = 3) -> list[np.ndarray]:
    # The whole-graph sweep clips the start of every trailing empty row
    # to nnz - 1, which cuts the last neighbour off the last non-empty
    # row: it is exact only when the last data vertex has a neighbour.
    assert data.degrees[-1] > 0
    return _ref_cfl_refine(query, data, cand, max_rounds)


def _ref_build_candidates(query: Graph, data: Graph) -> list[np.ndarray]:
    return _ref(query, data, nlf_filter(query, data, ldf_filter(query, data)))


def _same_lists(a: list[np.ndarray], b: list[np.ndarray]) -> bool:
    return len(a) == len(b) and all(
        x.dtype == y.dtype and np.array_equal(x, y) for x, y in zip(a, b))


def _path(labels: list[int]) -> Graph:
    return Graph.from_edges(len(labels),
                            [(i, i + 1) for i in range(len(labels) - 1)],
                            labels, max(labels) + 1)


# ---- cases --------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _human():
    data = human_like_graph(0)
    queries = [random_walk_query(data, k, seed=100 + i)
               for i, k in enumerate([8, 16, 8, 16, 8, 16])]
    return data, queries


def _isolated():
    """ER graph with isolated vertices at the front and in the middle:
    degree-0 vertices reach the sweep as candidates of vertices that
    have query neighbours when the lists are the label classes alone."""
    base = er_labeled_graph(60, 150, 3, seed=4)
    iso = {0, 1, 17, 30, 31, 45}
    keep = [v for v in range(base.n) if v not in iso]
    edges = [(a, int(b)) for a in keep for b in base.neighbors(a)
             if int(b) not in iso and a < b]
    data = Graph.from_edges(base.n, edges, base.labels, base.n_labels)
    assert (data.degrees == 0).sum() >= len(iso)
    queries = query_set(data, 5, 6, seed=2) + query_set(data, 3, 4, seed=9)
    return data, queries


def _empties_round1():
    """Path l0-l1-l2-l3 against two broken halves: NLF keeps one
    candidate per query vertex, and the first sweep empties u1's."""
    #    a(0) - b(1) - c(2)        d(1) - e(2) - f(3)
    data = Graph.from_edges(6, [(0, 1), (1, 2), (3, 4), (4, 5)],
                            [0, 1, 2, 1, 2, 3], 4)
    query = _path([0, 1, 2, 3])
    return data, [query]


def _max_rounds():
    """Path l0-...-l7 against a chain x0..x5 (labels 0..5) whose end has
    no candidate neighbour for u6: the removal walks back one query
    vertex a round against the sweep order, so three rounds stop short
    of the fixpoint."""
    #  x0 .. x5 chain, y(6) on x5; z6(6) - z7(7), w(5) - z6
    edges = [(i, i + 1) for i in range(5)] + [(5, 6), (7, 8), (9, 7)]
    labels = [0, 1, 2, 3, 4, 5, 6, 6, 7, 5]
    data = Graph.from_edges(10, edges, labels, 8)
    query = _path(list(range(8)))
    return data, [query]


def _er():
    data = er_labeled_graph(200, 800, 4, seed=11)
    queries = (query_set(data, 4, 5, seed=1) + query_set(data, 8, 5, seed=2)
               + query_set(data, 12, 3, seed=3))
    return data, queries


CASES = {"human_walk": _human, "isolated": _isolated,
         "empties_round1": _empties_round1, "max_rounds": _max_rounds,
         "er": _er}


@pytest.mark.parametrize("case", sorted(CASES))
def test_candidates_identical_to_full_graph_sweep(case):
    data, queries = CASES[case]()
    for q in queries:
        assert _same_lists(build_candidates(q, data),
                           _ref_build_candidates(q, data))
        # label classes alone: candidates of any degree enter the sweep
        by_label = [data.label_index[int(q.labels[u])] for u in range(q.n)]
        assert _same_lists(cfl_refine(q, data, by_label),
                           _ref(q, data, by_label))
    if case == "empties_round1":
        q = queries[0]
        first = _ref(q, data, ldf_filter(q, data), max_rounds=1)
        assert first[1].size == 0
        assert all(c.size == 0 for c in build_candidates(q, data))
    if case == "max_rounds":
        q = queries[0]
        cand = ldf_filter(q, data)
        bounded = cfl_refine(q, data, cand)
        fixpoint = cfl_refine(q, data, cand, max_rounds=20)
        assert not _same_lists(bounded, fixpoint)
        assert bounded[0].size and not fixpoint[0].size
    if case == "isolated":
        q = queries[0]
        by_label = [data.label_index[int(q.labels[u])] for u in range(q.n)]
        assert any((data.degrees[c] == 0).any() for c in by_label)


# ---- the neighbour-label table ------------------------------------------
def _fresh_counts(g: Graph) -> np.ndarray:
    counts = np.zeros((g.n, g.n_labels), dtype=np.int32)
    src = np.repeat(np.arange(g.n, dtype=np.int32), g.degrees)
    np.add.at(counts, (src, g.labels[g.indices]), 1)
    return counts


@pytest.mark.parametrize("case", ["isolated", "er", "empties_round1"])
def test_neighbor_label_counts_cached(case):
    data, queries = CASES[case]()
    for g in [data] + queries:
        first = g.neighbor_label_counts
        assert first.dtype == np.int32
        assert np.array_equal(first, _fresh_counts(g))
        assert g.neighbor_label_counts is first
        assert not first.flags.writeable


# ---- soundness against the label and degree filter alone ---------------
def _trailing_isolated():
    """Isolated vertices after the last non-empty CSR row: the sweep
    must still see that row's last neighbour (the whole-graph sweep cut
    it off and lost the only embedding)."""
    data = Graph.from_edges(5, [(0, 2), (1, 2)], [0, 1, 2, 3, 3], 4)
    query = Graph.from_edges(2, [(0, 1)], [2, 1], 4)
    return data, [query]


def _small_er():
    data = er_labeled_graph(40, 120, 3, seed=6)
    return data, query_set(data, 4, 8, seed=3) + query_set(data, 6, 4, seed=5)


@pytest.mark.parametrize("build", [_small_er, _trailing_isolated, _isolated,
                                   _max_rounds],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_every_embedding_within_candidates(build):
    data, queries = build()
    found = 0
    for q in queries:
        cand = build_candidates(q, data)
        res = backtrack_deadend(q, data, cand=ldf_filter(q, data), limit=None)
        for emb in res.embeddings:
            for u in range(q.n):
                assert int(emb[u]) in set(cand[u].tolist())
        found += len(res.embeddings)
        assert len(backtrack_deadend(q, data, limit=None).embeddings) == len(
            res.embeddings)
    if build is not _max_rounds:
        assert found > 0
