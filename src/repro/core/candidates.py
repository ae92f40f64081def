"""Candidate filtering for subgraph matching.

Produces, for every query vertex ``u``, the candidate set ``C[u]`` of data
vertices it may be mapped onto. Three filters of increasing strength, each
sound (never removes a vertex that participates in some embedding):

* LDF  — label + degree filter (Ullmann / Eq. 1 plus degree test).
* NLF  — neighbor-label-frequency filter (GraphQL/SPath style): ``v`` must
  have at least as many neighbors of each label as ``u`` does.
* CFL-lite — BFS-tree forward/backward refinement in the spirit of
  CFL-Match/TurboISO: a candidate survives only if every tree child/parent
  query vertex has at least one *adjacent* surviving candidate. Iterated to
  a fixpoint over the full query graph (stronger than tree-only).

The paper's method composes with these ("we can also combine our method and
structural analyses"); our default pipeline is LDF + NLF + CFL-lite, which
mirrors the paper's evaluation setup (they build on CFL-Match pruning).
"""
from __future__ import annotations

import numpy as np

from .graph import Graph


def ldf_filter(query: Graph, data: Graph) -> list[np.ndarray]:
    """Label + degree filter: C[u] = {v : l(v)=l(u), deg(v) >= deg(u)}."""
    out: list[np.ndarray] = []
    deg = data.degrees
    for u in range(query.n):
        lab = int(query.labels[u])
        cands = data.label_index.get(lab, np.empty(0, np.int32))
        cands = cands[deg[cands] >= query.degree(u)]
        out.append(np.sort(cands).astype(np.int32))
    return out


def nlf_filter(query: Graph, data: Graph,
               cand: list[np.ndarray]) -> list[np.ndarray]:
    """Neighbor-label-frequency refinement of an existing candidate list."""
    q_counts = query.neighbor_label_counts  # [nq, n_labels_q]
    d_counts = data.neighbor_label_counts   # [nd, n_labels_d]
    n_labels = min(q_counts.shape[1], d_counts.shape[1])
    out = []
    for u in range(query.n):
        need = q_counts[u]
        cands = cand[u]
        if len(cands) == 0:
            out.append(cands)
            continue
        have = d_counts[cands]
        ok = np.all(have[:, :n_labels] >= need[None, :n_labels], axis=1)
        # any query label beyond the data alphabet kills all candidates
        if need[n_labels:].any():
            ok &= False
        out.append(cands[ok])
    return out


def _refine_once(query: Graph, data: Graph,
                 cand_masks: list[np.ndarray]) -> bool:
    """One sweep of edge-consistency refinement (AC-ish / CFL passes).

    cand_masks[u] is a boolean mask over data vertices. A candidate v of u
    survives only if, for every query neighbor u', v has at least one data
    neighbor that is a candidate of u'. Returns True if anything changed.
    """
    changed = False
    indptr = data.indptr
    for u in range(query.n):
        mask_u = cand_masks[u]
        idx = np.flatnonzero(mask_u)
        nbrs_u = query.neighbors(u)
        if idx.size == 0 or nbrs_u.size == 0:
            continue
        # the CSR rows of u's candidates only, gathered once: the sweep
        # costs the sum of their degrees per query edge, not the whole
        # adjacency. A candidate of degree 0 has no neighbor to offer.
        row_start = indptr[idx].astype(np.int64)
        deg = indptr[idx + 1].astype(np.int64) - row_start
        keep = deg > 0
        seg_start = np.cumsum(deg) - deg
        flat = (np.repeat(row_start - seg_start, deg)
                + np.arange(int(deg.sum()), dtype=np.int64))
        nb = data.indices[flat]
        live = np.flatnonzero(keep)
        live_start = seg_start[live]
        for uq in nbrs_u:
            if not keep.any():
                break
            # v survives iff any neighbor of v is a candidate of u'
            hit = np.logical_or.reduceat(cand_masks[int(uq)][nb], live_start)
            keep[live] &= hit
        if not keep.all():
            changed = True
            mask_u[idx[~keep]] = False
    return changed


def cfl_refine(query: Graph, data: Graph, cand: list[np.ndarray],
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
        if not _refine_once(query, data, masks):
            break
    return [np.nonzero(m)[0].astype(np.int32) for m in masks]


def build_candidates(query: Graph, data: Graph,
                     use_nlf: bool = True,
                     use_cfl: bool = True) -> list[np.ndarray]:
    """Default filtering pipeline: LDF (+NLF) (+CFL-lite fixpoint)."""
    cand = ldf_filter(query, data)
    if use_nlf:
        cand = nlf_filter(query, data, cand)
    if use_cfl:
        cand = cfl_refine(query, data, cand)
    return cand
