"""Device-side programs of the TPU wave engine.

This module contains the *pure JAX* (jit-able, shard_map-able) functions
executed per wave step. The host scheduler in ``vectorized.py`` owns the
segment stacks and resolution bookkeeping; every array-heavy operation —
Eq. 2 bitmap refinement, injectivity masking, O(1) dead-end lookups over a
whole wave, child extraction, pattern scatter — happens here on fixed
shapes so a single compiled program serves every query.

Multi-query waves (DESIGN.md §2): per-query state lives in *banks* stacked
along a leading slot axis — :class:`QueryBank` ``[S, ...]`` and the
bounded hashed Δ store :class:`~repro.patterns.store.PatternStoreBank`
``[S, capacity]`` — and every wave row carries a ``query_slot`` and a
``depth`` lane, so one jitted program expands a wave whose rows belong to
many concurrent queries at different depths (and, with shard-as-segments,
to many shards of the same query). Sequential-style callers go through
the 1-slot ``WaveEngine`` facade; the launch dry-run lowers the real
multi-query program.

Design notes (see DESIGN.md §2):
  * adjacency and candidate sets are packed uint32 bitmaps; Eq. 2 becomes
    a gather + AND-reduction over mapped-neighbor rows (the Pallas kernel
    ``kernels/bitmap_refine.py`` implements the same contraction with
    explicit VMEM tiling; this file keeps the jnp reference path which
    XLA fuses well on CPU and is what the dry-run lowers by default).
  * dead-end masks are bitmasks over query order positions, two uint32
    words (supports |V_Q| <= 64).
  * the numeric pattern check Φ[μ] == φ (paper Eq. 7) is a hashed probe
    (``patterns.store.hash_probe``: multiplicative hash + PROBE-slot
    linear window), a gather and a compare, evaluated for every
    (row, extracted-child) pair of the wave in one shot. The store is
    O(configured capacity) — the last data-graph-sized resident array
    is gone — and lookups bump per-entry hit counters that guide
    eviction when an insert finds its probe window full.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..patterns.store import (MASK_WORDS, PatternStore, PatternStoreBank,
                              StoreCounters, hash_insert, hash_probe)
from . import spans

N_PAD = 64              # padded query size
FULL = np.uint32(0xFFFFFFFF)   # host constant: import starts no backend


class GraphArrays(NamedTuple):
    """Device view of the data graph.

    Two mutually exclusive adjacency layouts (DESIGN.md §2):

      * dense  — ``adj_bitmap`` holds the whole packed [V, W] block and
        the hier fields are None; refinement gathers rows directly (the
        small-|V| fast path whose kernel keeps the block in VMEM).
      * hier   — ``adj_bitmap`` is None and the two-level layout rides
        in ``adj_summary``/``chunk_ptr``/``chunk_id``/``chunk_data``
        (see core.graph.HierBitmap); refinement intersects summaries
        first and touches only live chunks, so the store can stay in
        HBM past the VMEM ceiling. ``chunk_pad`` is a dummy int32
        [kmax] lane whose *shape* carries the layout's static
        max-stored-chunks-per-row through jit.

    Which layout a graph gets is decided once at scheduler construction
    (kernels.config.use_hbm_adjacency); every refinement call branches
    at trace time on ``chunk_data is not None``.
    """
    adj_bitmap: jax.Array | None   # uint32 [V, W] packed adjacency
    n_vertices: jax.Array          # int32 scalar
    adj_summary: jax.Array | None = None  # uint32 [V, SW] chunk summary
    chunk_ptr: jax.Array | None = None    # int32 [V + 1] CSR over chunks
    chunk_id: jax.Array | None = None     # int32 [n_stored + kmax]
    chunk_data: jax.Array | None = None   # uint32 [n_stored + kmax, C]
    chunk_pad: jax.Array | None = None    # int32 [kmax] (shape-only lane)


class QueryBank(NamedTuple):
    """Per-slot query arrays for multi-query waves (query axis first)."""
    cand_bitmap: jax.Array   # uint32 [S, N_PAD, W]
    nbr_mask: jax.Array      # bool [S, N_PAD, N_PAD]
    n_query: jax.Array       # int32 [S]
    learn: jax.Array         # bool [S] — slot stores patterns in-loop

    @staticmethod
    def empty(n_slots: int, w: int) -> "QueryBank":
        return QueryBank(
            cand_bitmap=jnp.zeros((n_slots, N_PAD, w), jnp.uint32),
            nbr_mask=jnp.zeros((n_slots, N_PAD, N_PAD), bool),
            n_query=jnp.zeros((n_slots,), jnp.int32),
            learn=jnp.zeros((n_slots,), bool))


class WaveResultMQ(NamedTuple):
    """Multi-query wave result — per-row counters so the host can
    attribute prune/injectivity statistics to the owning query."""
    refined_empty: jax.Array     # bool [F]
    n_children: jax.Array        # int32 [F]
    n_leftover: jax.Array        # int32 [F]
    partial_mask: jax.Array      # uint32 [F, MASK_WORDS]
    child_v: jax.Array           # int32 [F, KPR]
    child_valid: jax.Array       # bool [F, KPR]
    leftover: jax.Array          # uint32 [F, W]
    n_pruned: jax.Array          # int32 [F] dead-end prunes per row
    n_inj: jax.Array             # int32 [F] injectivity kills per row
    pruned_v: jax.Array          # int32 [F, KPR] Δ-pruned children (-1 pad)
    #   the host folds pruned_v into per-key hit counters, which rank
    #   the deterministic cross-host pattern exchange (DESIGN.md §3)


def _popcount_rows(words: jax.Array) -> jax.Array:
    """Sum of set bits per row of a uint32 [..., W] array -> int32 [...]."""
    return lax.population_count(words).astype(jnp.int32).sum(axis=-1)


def _unpack_bits(words: jax.Array, v: int) -> jax.Array:
    """uint32 [F, W] -> bool [F, v]."""
    f, w = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(f, w * 32)[:, :v].astype(bool)


def _pack_bits(bits: jax.Array, w: int) -> jax.Array:
    """bool [F, v] -> uint32 [F, W] (zero-padded)."""
    f, v = bits.shape
    padded = jnp.zeros((f, w * 32), bool).at[:, :v].set(bits)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return (padded.reshape(f, w, 32).astype(jnp.uint32) * weights
            ).sum(axis=-1, dtype=jnp.uint32)


def _position_bit(p: jax.Array) -> jax.Array:
    """Order position (scalar) -> uint32 [MASK_WORDS] one-hot-bit mask."""
    word = p // 32
    bit = jnp.uint32(1) << (p % 32).astype(jnp.uint32)
    return jnp.where(jnp.arange(MASK_WORDS) == word, bit, jnp.uint32(0))


def _position_bits(p: jax.Array) -> jax.Array:
    """Order positions int32 [F] -> uint32 [F, MASK_WORDS] one-hot bits."""
    word = p // 32
    bit = jnp.uint32(1) << (p % 32).astype(jnp.uint32)
    return jnp.where(jnp.arange(MASK_WORDS)[None, :] == word[:, None],
                     bit[:, None], jnp.uint32(0))


def _below_bits(d: jax.Array) -> jax.Array:
    """Bitmask of all positions strictly below d, uint32 [MASK_WORDS]."""
    idx = jnp.arange(MASK_WORDS * 32)
    bits = idx < d
    return (bits.reshape(MASK_WORDS, 32).astype(jnp.uint32)
            * (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
            ).sum(axis=-1, dtype=jnp.uint32)


def _below_bits_rows(d: jax.Array) -> jax.Array:
    """Positions strictly below d, rowwise: int32 [F] -> uint32 [F, MW]."""
    idx = jnp.arange(MASK_WORDS * 32)
    bits = idx[None, :] < d[:, None]                        # [F, MW*32]
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (bits.reshape(-1, MASK_WORDS, 32).astype(jnp.uint32)
            * weights).sum(axis=-1, dtype=jnp.uint32)


def _pack_mask_rows(bits: jax.Array) -> jax.Array:
    """bool [F, N_PAD] position sets -> packed uint32 [F, MASK_WORDS]."""
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return (bits.reshape(-1, MASK_WORDS, 32).astype(jnp.uint32)
            * weights).sum(axis=-1, dtype=jnp.uint32)


def _bitlen32(x: jax.Array) -> jax.Array:
    """Highest set bit + 1 of a uint32 (0 for 0): bit-smear + popcount."""
    x = x | (x >> 1)
    x = x | (x >> 2)
    x = x | (x >> 4)
    x = x | (x >> 8)
    x = x | (x >> 16)
    return lax.population_count(x).astype(jnp.int32)


def _mask_bitlen(words: jax.Array) -> jax.Array:
    """Bit length of packed 64-bit masks, uint32 [F, MASK_WORDS] -> int32
    [F] (the paper's μ: highest Γ position below the key + 1)."""
    hi, lo = words[:, 1], words[:, 0]
    return jnp.where(hi != 0, 32 + _bitlen32(hi), _bitlen32(lo))


def _extract_topk_packed(live: jax.Array, kpr: int
                         ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Extract the ``kpr`` lowest set bits per row of a packed bitmap.

    Word-level replacement for the old dense ``_unpack_bits`` + cumsum +
    vmapped-nonzero ranking, which materialized an O(F·V) boolean matrix
    per wave. Each of the ``kpr`` steps isolates the lowest set bit via
    first-nonzero-word + ``word & -word`` — O(kpr·F·W) word ops with no
    dense unpack, and the packed leftovers fall out for free.

    Returns (child_v int32 [F, kpr] ascending with -1 padding,
             leftover uint32 [F, W], n_leftover int32 [F]).
    """
    f, w = live.shape
    rows = jnp.arange(f)

    def step(cur, _):
        nz = cur != 0                                        # [F, W]
        any_row = nz.any(axis=1)
        first_w = jnp.argmax(nz, axis=1).astype(jnp.int32)   # [F]
        word = cur[rows, first_w]                            # [F]
        lsb = word & (jnp.uint32(0) - word)
        bit_idx = lax.population_count(
            lsb - jnp.uint32(1)).astype(jnp.int32)
        child = jnp.where(any_row, first_w * 32 + bit_idx, -1)
        cleared = word & (word - jnp.uint32(1))
        cur = cur.at[rows, first_w].set(
            jnp.where(any_row, cleared, word))
        return cur, child

    leftover, children = lax.scan(step, live, None, length=kpr)
    return children.T, leftover, _popcount_rows(leftover)


# ===================================================================
# slot management: load one query (+ its Δ store) into a bank slot
# ===================================================================
# Donation everywhere the store bank is threaded: the bank is the one
# large mutable device structure, and without donation every program
# that returns it copies all seven [S, C] lanes per dispatch (~4x the
# useful work on the single-step path). Callers always replace their
# handle with the returned one, so the old buffers are dead by
# construction.
@functools.partial(jax.jit, donate_argnums=(0, 1))
def load_slot(qb: QueryBank, tb: PatternStoreBank, slot: jax.Array,
              cand_bitmap: jax.Array, nbr_mask: jax.Array,
              n_query: jax.Array, store: PatternStore,
              learn: jax.Array = True
              ) -> tuple[QueryBank, PatternStoreBank]:
    """Install a query in bank slot ``slot`` (admission). ``store`` is
    the slot's initial hashed Δ store: empty, or seeded with transferable
    patterns (template-cache warm start, checkpoint restore, cross-host
    import — see patterns.cache / core.distributed). ``learn`` gates the
    megastep's in-loop pattern stores for this slot."""
    qb2 = QueryBank(
        cand_bitmap=qb.cand_bitmap.at[slot].set(cand_bitmap),
        nbr_mask=qb.nbr_mask.at[slot].set(nbr_mask),
        n_query=qb.n_query.at[slot].set(n_query),
        learn=qb.learn.at[slot].set(learn))
    tb2 = PatternStoreBank(
        key_pos=tb.key_pos.at[slot].set(store.key_pos),
        key_v=tb.key_v.at[slot].set(store.key_v),
        phi=tb.phi.at[slot].set(store.phi),
        mu=tb.mu.at[slot].set(store.mu),
        mask=tb.mask.at[slot].set(store.mask),
        valid=tb.valid.at[slot].set(store.valid),
        hits=tb.hits.at[slot].set(store.hits))
    return qb2, tb2


@functools.partial(jax.jit, donate_argnums=(0, 1))
def load_slots(qb: QueryBank, tb: PatternStoreBank, slots: jax.Array,
               cand_bitmap: jax.Array, nbr_mask: jax.Array,
               n_query: jax.Array, store: PatternStore,
               learn: jax.Array) -> tuple[QueryBank, PatternStoreBank]:
    """Batch variant of :func:`load_slot`: install ``k`` queries in one
    dispatch (all row arguments carry a leading [k] axis; a ``slots``
    value of S drops that row). An admission burst — fresh server, batch
    submit — used to pay one jit dispatch per query, which dominated
    tiny-batch admission latency."""
    qb2 = QueryBank(
        cand_bitmap=qb.cand_bitmap.at[slots].set(cand_bitmap,
                                                 mode="drop"),
        nbr_mask=qb.nbr_mask.at[slots].set(nbr_mask, mode="drop"),
        n_query=qb.n_query.at[slots].set(n_query, mode="drop"),
        learn=qb.learn.at[slots].set(learn, mode="drop"))
    tb2 = PatternStoreBank(
        key_pos=tb.key_pos.at[slots].set(store.key_pos, mode="drop"),
        key_v=tb.key_v.at[slots].set(store.key_v, mode="drop"),
        phi=tb.phi.at[slots].set(store.phi, mode="drop"),
        mu=tb.mu.at[slots].set(store.mu, mode="drop"),
        mask=tb.mask.at[slots].set(store.mask, mode="drop"),
        valid=tb.valid.at[slots].set(store.valid, mode="drop"),
        hits=tb.hits.at[slots].set(store.hits, mode="drop"))
    return qb2, tb2


@jax.jit
def read_store_slot(tb: PatternStoreBank, slot: jax.Array) -> PatternStore:
    """Read one slot's Δ store back out (pattern export on completion).

    Jitted so the export is ONE dispatch: seven separate ``tb.x[slot]``
    gathers cost ~1ms of host dispatch time per finished query, which
    dominated the tiny-workload serving smoke run."""
    return PatternStore(key_pos=tb.key_pos[slot], key_v=tb.key_v[slot],
                        phi=tb.phi[slot], mu=tb.mu[slot],
                        mask=tb.mask[slot], valid=tb.valid[slot],
                        hits=tb.hits[slot])


# ===================================================================
# multi-query wave programs
# ===================================================================
def _refine_hier_jnp(g: GraphArrays, acc0: jax.Array, frontier: jax.Array,
                     active: jax.Array) -> jax.Array:
    """Hierarchical Eq. 2 contraction in plain jnp.

    Each active position reconstructs its frontier rows from their
    stored chunks — an [F, kmax, C] gather proportional to the sparse
    layout, never the [F, NP, W] dense gather that costs W ∝ V per row
    (128 MB per wave at 64K vertices). The position loop runs to the
    deepest active position (traced bound), not N_PAD.
    """
    f, w = acc0.shape
    c = g.chunk_data.shape[1]
    kmax = g.chunk_pad.shape[0]
    ncp = g.adj_summary.shape[1] * 32
    acc = acc0.astype(jnp.uint32)
    hi = jnp.max(jnp.where(active.any(axis=0),
                           jnp.arange(N_PAD, dtype=jnp.int32) + 1, 0))

    def body(p, acc):
        vtx = frontier[:, p]
        act = (active[:, p] != 0) & (vtx >= 0)
        k0 = g.chunk_ptr[vtx.clip(0)]
        nk = g.chunk_ptr[vtx.clip(0) + 1] - k0
        ks = k0[:, None] + jnp.arange(kmax)[None, :]
        km = jnp.arange(kmax)[None, :] < nk[:, None]
        ids = jnp.where(km, g.chunk_id[ks], ncp)        # pad -> dropped
        data = jnp.where(km[:, :, None],
                         g.chunk_data[ks].astype(jnp.uint32),
                         jnp.uint32(0))
        rows = jnp.zeros((f, ncp, c), jnp.uint32).at[
            jnp.arange(f)[:, None], ids].set(data, mode="drop")
        rows = rows.reshape(f, ncp * c)[:, :w]
        return jnp.where(act[:, None], acc & rows, acc)

    return lax.fori_loop(0, hi, body, acc)


def refine_eq2_mq(g: GraphArrays, qb: QueryBank, query_slot: jax.Array,
                  frontier: jax.Array, depth: jax.Array,
                  backend: str = "jnp",
                  block_f: int | None = None,
                  dma_depth: int | None = None) -> jax.Array:
    """Eq. 2 candidate refinement for a mixed-query wave.

    C'(row) = cand[qid, depth] ∩ ⋂_{p < depth, p ~q depth} N(frontier[p]).
    ``query_slot`` and ``depth`` are int32 [F] lanes. Returns the packed
    candidate bitmap uint32 [F, W].

    ``backend`` (static, from ``kernels.config``): "jnp" keeps the inline
    gather + AND contraction that XLA fuses well on CPU; "pallas" /
    "pallas_interpret" lower to the multi-row ``bitmap_refine`` kernel,
    so one config switch moves the whole engine hot path onto the
    compiled kernel (no silent interpret-mode fallback).

    The adjacency layout picks the variant at trace time: a hierarchical
    ``g`` (``chunk_data`` set, ``adj_bitmap`` None) routes to the
    HBM-paged kernel / the sparse-gather jnp contraction; ``dma_depth``
    is its pipeline depth (None = tuned/config default).
    """
    acc0 = qb.cand_bitmap[query_slot, depth]                 # [F, W]
    pos = jnp.arange(N_PAD)
    active = (qb.nbr_mask[query_slot, depth]
              & (pos[None, :] < depth[:, None]))             # [F, NP]

    if g.chunk_data is not None:
        if backend != "jnp":
            from ..kernels.bitmap_refine import refine_bitmap_rows_hier
            w = acc0.shape[1]
            out = refine_bitmap_rows_hier(
                g.adj_summary, g.chunk_ptr, g.chunk_id, g.chunk_data,
                acc0, frontier, active,
                interpret=(backend == "pallas_interpret"),
                dma_depth=dma_depth)
            return out[:, :w].astype(jnp.uint32)
        return _refine_hier_jnp(g, acc0, frontier, active)

    if backend != "jnp":
        from ..kernels.bitmap_refine import refine_bitmap_rows
        w = acc0.shape[1]
        out = refine_bitmap_rows(g.adj_bitmap, acc0, frontier, active,
                                 interpret=(backend == "pallas_interpret"),
                                 block_f=block_f)
        return out[:, :w].astype(jnp.uint32)

    # one gather + reduce instead of a fori_loop over positions: 64
    # sequential [F, W] dispatches cost more than the [F, NP, W] gather
    rows = g.adj_bitmap[frontier.clip(0)]                    # [F, NP, W]
    rows = jnp.where(active[:, :, None], rows, FULL)
    return acc0 & lax.reduce(rows, FULL, lax.bitwise_and, (1,))


def deadend_lookup_children_mq(tb: PatternStoreBank, phi: jax.Array,
                               query_slot: jax.Array, depth: jax.Array,
                               child_v: jax.Array
                               ) -> tuple[jax.Array, jax.Array,
                                          PatternStoreBank]:
    """Paper-Eq.7 check for extracted children only (§Perf iteration 2:
    O(F·kpr·PROBE) hashed probes instead of the O(F·V) dense sweep),
    store rows keyed per query slot.

    child_v: int32 [F, KPR] candidate vertices (-1 = empty slot).
    Returns (prune bool [F, KPR], Γ* contribution uint32 [F, MASK_WORDS],
    the store bank with the matched entries' hit counters bumped — the
    counters feed eviction ranking and the host's exchange/cache
    ranking, so lookups thread the bank functionally).
    """
    f, kpr = child_v.shape
    cv = child_v.clip(0).reshape(-1)                        # [F*KPR]
    sl = jnp.broadcast_to(query_slot[:, None], (f, kpr)).reshape(-1)
    kp = jnp.broadcast_to(depth[:, None], (f, kpr)).reshape(-1)
    found, phi_g, mu_g, mask_g, idx = hash_probe(tb, sl, kp, cv)
    valid_g = found.reshape(f, kpr) & (child_v >= 0)
    my_phi = jnp.take_along_axis(phi, mu_g.reshape(f, kpr), axis=1)
    prune = valid_g & (my_phi == phi_g.reshape(f, kpr))
    masks = mask_g.reshape(f, kpr, MASK_WORDS)
    masks = jnp.where(prune[:, :, None],
                      masks | _position_bits(depth)[:, None, :],
                      jnp.uint32(0))
    # OR over the (small) child axis via unpack -> any -> repack
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = ((masks[:, :, :, None] >> shifts) & jnp.uint32(1)) > 0
    got = bits.any(axis=1)                   # [F, MASK_WORDS, 32]
    weights = jnp.uint32(1) << shifts
    contrib = (got.astype(jnp.uint32) * weights).sum(
        axis=-1, dtype=jnp.uint32)           # [F, MASK_WORDS]
    n_slots = tb.valid.shape[0]
    hit_slot = jnp.where(prune.reshape(-1), sl, n_slots)   # miss -> dropped
    tb2 = tb._replace(hits=tb.hits.at[hit_slot, idx].add(1, mode="drop"))
    return prune, contrib, tb2


def _expand_rows(g: GraphArrays, qb: QueryBank, tb: PatternStoreBank,
                 frontier: jax.Array, used: jax.Array, phi: jax.Array,
                 row_valid: jax.Array, query_slot: jax.Array,
                 depth: jax.Array, kpr: int,
                 backend: str = "jnp", block_f: int | None = None,
                 dma_depth: int | None = None
                 ) -> tuple[WaveResultMQ, PatternStoreBank]:
    """One expansion pass over F mixed-query rows (shared by
    :func:`expand_wave_mq` and the megastep loop body): Eq. 2 refinement,
    injectivity Γ* terms, packed top-kpr child extraction, and the
    Lemma 3 / Eq. 7 dead-end check on the extracted children. Returns
    the wave result plus the store bank with lookup hit counters
    bumped."""
    f = frontier.shape[0]

    refined = refine_eq2_mq(g, qb, query_slot, frontier, depth,
                            backend, block_f, dma_depth)     # [F, W]
    refined = jnp.where(row_valid[:, None], refined, jnp.uint32(0))
    refined_empty = (_popcount_rows(refined) == 0) & row_valid

    # ---- injectivity: candidates already used by the row ---------------
    inj_words = refined & used                               # [F, W]
    n_inj_per_row = _popcount_rows(inj_words)

    # injectivity Γ* contribution (Lemma 2): for every mapped position p
    # whose vertex is a refined candidate, add bit(p) | bit(depth).
    depth_bits = _position_bits(depth)                       # [F, MW]

    def inj_body(p, acc):
        vert = frontier[:, p].clip(0)                        # [F]
        word = jnp.take_along_axis(refined, (vert // 32)[:, None],
                                   axis=1)[:, 0]
        hit = ((word >> (vert % 32).astype(jnp.uint32)) & 1).astype(bool)
        hit &= (p < depth) & row_valid
        contrib = _position_bit(p)[None, :] | depth_bits
        return jnp.where(hit[:, None], acc | contrib, acc)

    inj_mask = lax.fori_loop(
        0, N_PAD, inj_body,
        jnp.zeros((f, MASK_WORDS), jnp.uint32))

    # ---- extract candidate children (per-row cap, packed ranking) -------
    live = refined & ~used                                   # [F, W]
    child_v, leftover, n_leftover = _extract_topk_packed(live, kpr)

    # ---- dead-end pruning on extracted children (Lemma 3 / Eq. 7) -------
    # Perf iteration 2 (see EXPERIMENTS.md): checking only extracted
    # children turns the O(F*V) dense sweep into O(F*kpr) gathers;
    # prunable candidates still in `leftover` are checked when a later
    # pass extracts them.
    prune, prune_mask, tb = deadend_lookup_children_mq(
        tb, phi, query_slot, depth, child_v)
    child_valid = (child_v >= 0) & ~prune
    n_children = child_valid.sum(axis=1).astype(jnp.int32)
    partial_mask = inj_mask | prune_mask

    return WaveResultMQ(
        refined_empty=refined_empty,
        n_children=n_children,
        n_leftover=n_leftover,
        partial_mask=partial_mask,
        child_v=jnp.where(child_valid, child_v, -1),
        child_valid=child_valid,
        leftover=leftover,
        n_pruned=jnp.where(row_valid, prune.sum(axis=1), 0),
        n_inj=jnp.where(row_valid, n_inj_per_row, 0),
        pruned_v=jnp.where(prune & row_valid[:, None], child_v, -1),
    ), tb


@functools.partial(jax.jit, donate_argnums=(2,),
                   static_argnames=("kpr", "backend", "block_f",
                                    "dma_depth"))
def expand_wave_mq(g: GraphArrays, qb: QueryBank, tb: PatternStoreBank,
                   frontier: jax.Array, used: jax.Array, phi: jax.Array,
                   row_valid: jax.Array, query_slot: jax.Array,
                   depth: jax.Array, kpr: int = 16,
                   backend: str = "jnp", block_f: int = 8,
                   dma_depth: int | None = None
                   ) -> tuple[WaveResultMQ, PatternStoreBank]:
    """Expand every row of a mixed-query wave by one query position.

    Args:
      frontier:   int32 [F, N_PAD] mapped data vertex per order position
                  (-1 where unmapped).
      used:       uint32 [F, W] bitmap of data vertices used by the row.
      phi:        int32 [F, N_PAD + 1] ancestor embedding ids (Φ array).
      row_valid:  bool [F] padding mask.
      query_slot: int32 [F] — owning query's bank slot, per row.
      depth:      int32 [F] — number of mapped positions, per row.
      kpr:        static per-row child cap for this pass (leftovers are
                  re-expanded by the host in later passes).
      backend:    static kernel backend for the Eq. 2 contraction.

    Returns (result, store bank with Δ lookup hit counters bumped).
    """
    return _expand_rows(g, qb, tb, frontier, used, phi, row_valid,
                        query_slot, depth, kpr, backend, block_f,
                        dma_depth)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("kpr",))
def extract_more_mq(tb: PatternStoreBank, phi: jax.Array,
                    query_slot: jax.Array, depth: jax.Array,
                    leftover: jax.Array, kpr: int = 64
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                               jax.Array, jax.Array, jax.Array,
                               PatternStoreBank]:
    """Extract up to ``kpr`` more children per row from leftover bitmaps
    of a mixed-query wave.

    Leftover bits already survived refinement and injectivity in their
    fresh pass; the dead-end check runs here at extraction time (and may
    see *newer* patterns than the fresh pass did — strictly more pruning).
    Returns (child_v, child_valid, new_leftover, n_leftover,
             partial_mask, n_pruned[F], pruned_v[F, KPR], tb).
    """
    child_v, new_leftover, n_leftover = _extract_topk_packed(leftover, kpr)
    prune, prune_mask, tb = deadend_lookup_children_mq(
        tb, phi, query_slot, depth, child_v)
    child_valid = (child_v >= 0) & ~prune
    return (jnp.where(child_valid, child_v, -1), child_valid,
            new_leftover, n_leftover, prune_mask, prune.sum(axis=1),
            jnp.where(prune, child_v, -1), tb)


@jax.jit
def assemble_children_mq(frontier: jax.Array, used: jax.Array,
                         phi: jax.Array, child_v: jax.Array,
                         child_valid: jax.Array, depth: jax.Array,
                         id_base: jax.Array
                         ) -> tuple[jax.Array, jax.Array, jax.Array,
                                    jax.Array, jax.Array]:
    """Materialize child rows [F*KPR, ...] from a mixed-query wave result.

    ``depth`` is the per-row int32 [F] lane. Returns (child_frontier,
    child_used, child_phi, parent_row, valid) — padded flat arrays; the
    host compacts them into new per-query segments. Fresh embedding ids
    are drawn from one shared counter (``id_base``): ids only need to be
    unique within a query, so global uniqueness is sufficient.
    """
    f, kpr = child_v.shape
    flat_v = child_v.reshape(-1)                              # [F*KPR]
    valid = child_valid.reshape(-1)
    parent = jnp.repeat(jnp.arange(f, dtype=jnp.int32), kpr)
    d_par = depth[parent]                                     # [F*KPR]
    cf = frontier[parent]                                     # [F*KPR, NP]
    cf = jnp.where(
        (jnp.arange(cf.shape[1])[None, :] == d_par[:, None]) & valid[:, None],
        flat_v[:, None], cf)
    vv = flat_v.clip(0)
    word = (vv // 32).astype(jnp.int32)
    bit = jnp.uint32(1) << (vv % 32).astype(jnp.uint32)
    cu = used[parent]
    add = jnp.zeros_like(cu).at[jnp.arange(cu.shape[0]), word].set(
        jnp.where(valid, bit, jnp.uint32(0)))
    cu = cu | add
    new_ids = id_base + jnp.cumsum(valid.astype(jnp.int32)) - 1
    cp = phi[parent]
    cp = jnp.where(
        (jnp.arange(cp.shape[1])[None, :] == d_par[:, None] + 1)
        & valid[:, None],
        new_ids[:, None], cp)
    return cf, cu, cp, parent, valid


@functools.partial(jax.jit, donate_argnums=(0,))
def store_patterns_mq(tb: PatternStoreBank, query_slot: jax.Array,
                      key_pos: jax.Array, key_v: jax.Array,
                      phis: jax.Array, mus: jax.Array, masks: jax.Array,
                      valid: jax.Array
                      ) -> tuple[PatternStoreBank, StoreCounters]:
    """Batched Δ[slot, (u_k, v)] <- (φ, μ, Γ) hashed insert (paper Eq. 6)
    across all slots at once.

    Invalid (padding) entries are routed out of bounds and dropped, so
    they can never clobber a real pattern. Returns the updated bank and
    per-slot insert counters (stored / overwrites / evictions / in-batch
    drops) — eviction is counter-guided and always sound (advisory-table
    invariant: losing a pattern only loses pruning, see patterns.store).
    """
    return hash_insert(tb, query_slot, key_pos, key_v, phis, mus, masks,
                       valid)


# ===================================================================
# fused multi-step megastep (DESIGN.md §2 "megastep & async pipeline")
# ===================================================================
class MegaResult(NamedTuple):
    """Digest of one K-depth megastep.

    The ring buffer rows [0, F) are the host's input wave; rows
    [F, tail) were created in-loop. Rows [0, head) were expanded
    in-loop; rows [head, tail) ran out of depth/capacity budget and are
    returned *pending* — the host re-packs them into fresh segments, so
    no work is ever lost to an overflow. All per-row lanes are indexed
    by ring position and are zero for rows never expanded.
    """
    tb: PatternStoreBank         # updated (host flush + in-loop stores)
    buf_frontier: jax.Array      # int32 [C, N_PAD]
    buf_used: jax.Array          # uint32 [C, W]
    buf_phi: jax.Array           # int32 [C, N_PAD + 1]
    buf_slot: jax.Array          # int32 [C]
    buf_depth: jax.Array         # int32 [C]
    buf_parent: jax.Array        # int32 [C] ring index of parent (-1: input)
    buf_valid: jax.Array         # bool [C]
    head: jax.Array              # int32 — rows [0, head) were expanded
    tail: jax.Array              # int32 — rows [head, tail) pending
    refined_empty: jax.Array     # bool [C] Lemma-1 dead (Eq. 2 empty)
    n_children: jax.Array        # int32 [C] surviving children appended
    n_leftover: jax.Array        # int32 [C]
    leftover: jax.Array          # uint32 [C, W]
    partial_mask: jax.Array      # uint32 [C, MASK_WORDS] inj+prune Γ* terms
    n_pruned: jax.Array          # int32 [C]
    n_inj: jax.Array             # int32 [C]
    n_emb_row: jax.Array         # int32 [C] embeddings emitted by the row
    dev_stored: jax.Array        # bool [C] Lemma-1 pattern stored in-loop
    pruned_v: jax.Array          # int32 [C, KPR] Δ-pruned children (-1 pad)
    # per-slot work-item accounting: how much of the dispatch each
    # resident query actually consumed (drives shard/occupancy reports)
    slot_rows: jax.Array         # int32 [S] rows expanded per slot
    slot_children: jax.Array     # int32 [S] rows+embeddings created per slot
    # per-slot Δ store insert accounting (host flush + in-loop stores of
    # this dispatch; occupancy is read off the live bank at report time)
    pat_stored: jax.Array        # int32 [S]
    pat_overwrites: jax.Array    # int32 [S]
    pat_evictions: jax.Array     # int32 [S]
    pat_dropped: jax.Array       # int32 [S]
    emb_frontier: jax.Array      # int32 [emb_cap, N_PAD] found embeddings
    emb_slot: jax.Array          # int32 [emb_cap]
    n_emb: jax.Array             # int32
    n_ids: jax.Array             # int32 fresh embedding ids consumed


@functools.partial(jax.jit, donate_argnums=(2,), static_argnames=(
    "kpr", "k_depth", "capacity", "emb_cap", "backend", "block_f",
    "dma_depth"))
def run_megastep_mq(g: GraphArrays, qb: QueryBank, tb: PatternStoreBank,
                    frontier: jax.Array, used: jax.Array, phi: jax.Array,
                    row_valid: jax.Array, query_slot: jax.Array,
                    depth: jax.Array,
                    st_slot: jax.Array, st_kpos: jax.Array,
                    st_kv: jax.Array, st_phi: jax.Array, st_mu: jax.Array,
                    st_mask: jax.Array, st_valid: jax.Array,
                    id_base: jax.Array, learn_enabled: jax.Array,
                    kpr: int = 8, k_depth: int = 4, capacity: int = 1024,
                    emb_cap: int = 512, backend: str = "jnp",
                    block_f: int = 8,
                    dma_depth: int | None = None) -> MegaResult:
    """Fused expand → assemble → pattern-store over up to ``k_depth``
    consecutive depth-steps, one host round-trip.

    A device-resident ring buffer holds the frontier/used/phi/slot/depth
    lanes of every live row. Each ``lax.while_loop`` iteration pops one
    F-row chunk off the head, expands it (`_expand_rows`), assembles the
    surviving non-last-level children directly at the tail, emits
    last-level children into an embedding buffer, and — for rows whose
    Eq. 2 candidate set came back empty — scatters their Lemma-1
    dead-end pattern ``(φ, μ, Γ = N(u_d) ∩ dom(M̂))`` straight into Δ,
    so later iterations of the *same* dispatch already prune on it.
    The host's batched pattern flush (``st_*``, fixed-length padded with
    a validity lane) is applied before the first iteration, replacing
    the separate ``store_patterns_mq`` dispatch of the single-step path.

    The loop stops when the queue drains, ``k_depth`` chunks were
    expanded, or a conservative worst-case bound (``F·kpr`` appends /
    embeddings per chunk) could overflow the ring or embedding buffer;
    everything still pending is returned in the digest. Fresh embedding
    ids are drawn from ``id_base``; the host reserves the worst case
    (``capacity - F``) so a later dispatch can be issued before this
    digest is read (async double-buffering).

    deep dive: Lemma-4 *aggregated* patterns still resolve on the host
    (they need the row's whole subtree), riding the next dispatch via
    the fused flush — only the immediate Lemma-1 stores move in-loop.
    """
    f_step, w = used.shape
    c = capacity
    assert c >= f_step * (kpr + 1), "ring cannot hold one chunk's children"
    assert emb_cap >= f_step * kpr, "emb buffer cannot hold one chunk"

    # ---- host-batched pattern stores ride the dispatch -----------------
    tb, pat0 = store_patterns_mq(tb, st_slot, st_kpos, st_kv, st_phi,
                                 st_mu, st_mask, st_valid)

    buf_frontier = jnp.full((c, N_PAD), -1, jnp.int32).at[:f_step].set(
        frontier)
    buf_used = jnp.zeros((c, w), jnp.uint32).at[:f_step].set(used)
    buf_phi = jnp.zeros((c, N_PAD + 1), jnp.int32).at[:f_step].set(phi)
    buf_slot = jnp.zeros((c,), jnp.int32).at[:f_step].set(query_slot)
    buf_depth = jnp.zeros((c,), jnp.int32).at[:f_step].set(depth)
    buf_parent = jnp.full((c,), -1, jnp.int32)
    buf_valid = jnp.zeros((c,), bool).at[:f_step].set(row_valid)

    zi = jnp.zeros((c,), jnp.int32)
    n_slots = qb.n_query.shape[0]
    lanes0 = dict(
        refined_empty=jnp.zeros((c,), bool), n_children=zi,
        n_leftover=zi, leftover=jnp.zeros((c, w), jnp.uint32),
        partial_mask=jnp.zeros((c, MASK_WORDS), jnp.uint32),
        n_pruned=zi, n_inj=zi, n_emb_row=zi,
        dev_stored=jnp.zeros((c,), bool),
        pruned_v=jnp.full((c, kpr), -1, jnp.int32),
        slot_rows=jnp.zeros((n_slots,), jnp.int32),
        slot_children=jnp.zeros((n_slots,), jnp.int32))

    state = dict(
        tb=tb, buf_frontier=buf_frontier, buf_used=buf_used,
        buf_phi=buf_phi, buf_slot=buf_slot, buf_depth=buf_depth,
        buf_parent=buf_parent, buf_valid=buf_valid,
        head=jnp.int32(0), tail=jnp.int32(f_step), it=jnp.int32(0),
        emb_frontier=jnp.full((emb_cap, N_PAD), -1, jnp.int32),
        emb_slot=jnp.zeros((emb_cap,), jnp.int32),
        n_emb=jnp.int32(0), id_ctr=jnp.asarray(id_base, jnp.int32),
        pat=pat0,
        **lanes0)

    def cond(s):
        return ((s["head"] < s["tail"]) & (s["it"] < k_depth)
                & (s["tail"] + f_step * kpr <= c)
                & (s["n_emb"] + f_step * kpr <= emb_cap))

    def body(s):
        head, tail = s["head"], s["tail"]
        cf = lax.dynamic_slice_in_dim(s["buf_frontier"], head, f_step)
        cu = lax.dynamic_slice_in_dim(s["buf_used"], head, f_step)
        cp = lax.dynamic_slice_in_dim(s["buf_phi"], head, f_step)
        slot_c = lax.dynamic_slice_in_dim(s["buf_slot"], head, f_step)
        depth_c = lax.dynamic_slice_in_dim(s["buf_depth"], head, f_step)
        in_chunk = (jnp.arange(f_step) + head) < tail
        valid_c = in_chunk & lax.dynamic_slice_in_dim(
            s["buf_valid"], head, f_step)

        res, tb_l = _expand_rows(g, qb, s["tb"], cf, cu, cp, valid_c,
                                 slot_c, depth_c, kpr, backend, block_f,
                                 dma_depth)

        is_last = depth_c + 1 == qb.n_query[slot_c]          # [F]

        # ---- materialize all surviving children (flat) -----------------
        parent_local = jnp.repeat(jnp.arange(f_step, dtype=jnp.int32), kpr)
        flat_v = res.child_v.reshape(-1)
        cvalid_flat = res.child_valid.reshape(-1)
        d_par = depth_c[parent_local]
        pos = jnp.arange(N_PAD)
        cf2 = cf[parent_local]
        cf2 = jnp.where((pos[None, :] == d_par[:, None])
                        & cvalid_flat[:, None], flat_v[:, None], cf2)
        vv = flat_v.clip(0)
        word = (vv // 32).astype(jnp.int32)
        bit = jnp.uint32(1) << (vv % 32).astype(jnp.uint32)
        cu2 = cu[parent_local]
        add = jnp.zeros_like(cu2).at[
            jnp.arange(cu2.shape[0]), word].set(
                jnp.where(cvalid_flat, bit, jnp.uint32(0)))
        cu2 = cu2 | add

        # ---- embeddings: last-level children go to the emb buffer ------
        emb_valid = cvalid_flat & is_last[parent_local]
        emb_off = jnp.cumsum(emb_valid.astype(jnp.int32)) - 1
        emb_idx = jnp.where(emb_valid, s["n_emb"] + emb_off, emb_cap)
        emb_frontier = s["emb_frontier"].at[emb_idx].set(cf2, mode="drop")
        emb_slot = s["emb_slot"].at[emb_idx].set(
            slot_c[parent_local], mode="drop")
        n_emb_new = emb_valid.sum().astype(jnp.int32)
        n_emb_row_c = (res.child_valid
                       & is_last[:, None]).sum(axis=1).astype(jnp.int32)

        # ---- append non-last children at the tail ----------------------
        app_valid = cvalid_flat & ~is_last[parent_local]
        app_off = jnp.cumsum(app_valid.astype(jnp.int32)) - 1
        app_idx = jnp.where(app_valid, tail + app_off, c)
        new_ids = s["id_ctr"] + app_off
        pos_phi = jnp.arange(N_PAD + 1)
        cp2 = cp[parent_local]
        cp2 = jnp.where((pos_phi[None, :] == d_par[:, None] + 1)
                        & app_valid[:, None], new_ids[:, None], cp2)
        n_new = app_valid.sum().astype(jnp.int32)
        bf = s["buf_frontier"].at[app_idx].set(cf2, mode="drop")
        bu = s["buf_used"].at[app_idx].set(cu2, mode="drop")
        bp = s["buf_phi"].at[app_idx].set(cp2, mode="drop")
        bs = s["buf_slot"].at[app_idx].set(
            slot_c[parent_local], mode="drop")
        bd = s["buf_depth"].at[app_idx].set(d_par + 1, mode="drop")
        bpar = s["buf_parent"].at[app_idx].set(
            head + parent_local, mode="drop")
        bv = s["buf_valid"].at[app_idx].set(True, mode="drop")
        n_child_c = (res.child_valid
                     & ~is_last[:, None]).sum(axis=1).astype(jnp.int32)

        # ---- in-loop Lemma-1 stores (Eq. 2 came back empty) ------------
        do_store = (res.refined_empty & (depth_c >= 1)
                    & qb.learn[slot_c] & learn_enabled)
        qnbr = _pack_mask_rows(qb.nbr_mask[slot_c, depth_c])
        gamma_w = qnbr & _below_bits_rows(depth_c)           # [F, MW]
        key_pos = (depth_c - 1).clip(0)
        key_v = jnp.take_along_axis(cf, key_pos[:, None], axis=1)[:, 0]
        mu = _mask_bitlen(gamma_w & _below_bits_rows(key_pos))
        phi_id = jnp.take_along_axis(cp, mu[:, None], axis=1)[:, 0]
        tb2, pat_c = store_patterns_mq(tb_l, slot_c, key_pos, key_v,
                                       phi_id, mu, gamma_w, do_store)

        # ---- digest lanes for this chunk -------------------------------
        def put(lane, vals):
            return lax.dynamic_update_slice_in_dim(lane, vals, head, 0)

        msk = valid_c

        def m1(x):
            return jnp.where(msk, x, jnp.zeros_like(x))

        def m2(x):
            return jnp.where(msk[:, None], x, jnp.zeros_like(x))

        return dict(
            tb=tb2, buf_frontier=bf, buf_used=bu, buf_phi=bp,
            buf_slot=bs, buf_depth=bd, buf_parent=bpar, buf_valid=bv,
            head=jnp.minimum(head + f_step, tail), tail=tail + n_new,
            it=s["it"] + 1, emb_frontier=emb_frontier, emb_slot=emb_slot,
            n_emb=s["n_emb"] + n_emb_new, id_ctr=s["id_ctr"] + n_new,
            pat=s["pat"].add(pat_c),
            refined_empty=put(s["refined_empty"], res.refined_empty),
            n_children=put(s["n_children"], m1(n_child_c)),
            n_leftover=put(s["n_leftover"], m1(res.n_leftover)),
            leftover=put(s["leftover"], m2(res.leftover)),
            partial_mask=put(s["partial_mask"], m2(res.partial_mask)),
            n_pruned=put(s["n_pruned"], m1(res.n_pruned)),
            n_inj=put(s["n_inj"], m1(res.n_inj)),
            n_emb_row=put(s["n_emb_row"], m1(n_emb_row_c)),
            dev_stored=put(s["dev_stored"], m1(do_store)),
            pruned_v=put(s["pruned_v"],
                         jnp.where(msk[:, None], res.pruned_v, -1)),
            slot_rows=s["slot_rows"].at[slot_c].add(
                valid_c.astype(jnp.int32)),
            slot_children=s["slot_children"].at[slot_c].add(
                m1(n_child_c + n_emb_row_c)))

    s = lax.while_loop(cond, body, state)
    return MegaResult(
        tb=s["tb"], buf_frontier=s["buf_frontier"], buf_used=s["buf_used"],
        buf_phi=s["buf_phi"], buf_slot=s["buf_slot"],
        buf_depth=s["buf_depth"], buf_parent=s["buf_parent"],
        buf_valid=s["buf_valid"], head=s["head"], tail=s["tail"],
        refined_empty=s["refined_empty"], n_children=s["n_children"],
        n_leftover=s["n_leftover"], leftover=s["leftover"],
        partial_mask=s["partial_mask"], n_pruned=s["n_pruned"],
        n_inj=s["n_inj"], n_emb_row=s["n_emb_row"],
        dev_stored=s["dev_stored"], pruned_v=s["pruned_v"],
        slot_rows=s["slot_rows"], slot_children=s["slot_children"],
        pat_stored=s["pat"].stored, pat_overwrites=s["pat"].overwrites,
        pat_evictions=s["pat"].evictions, pat_dropped=s["pat"].dropped,
        emb_frontier=s["emb_frontier"],
        emb_slot=s["emb_slot"], n_emb=s["n_emb"],
        n_ids=s["id_ctr"] - jnp.asarray(id_base, jnp.int32))


# ===================================================================
# device-resident frontier stacks (DESIGN.md §2 "device-resident state")
# ===================================================================
# Entry states of the per-slot stack. FREE entries are allocatable;
# FRESH/LEFT entries are pending work (each is on the slot's pending
# LIFO exactly once); WAIT entries were expanded and wait for their
# allocated children to resolve (Lemma 4 aggregation); RES entries hold
# a *converted* Γ ready to fold into their parent and be freed.
STK_FREE = 0
STK_FRESH = 1
STK_LEFT = 2
STK_WAIT = 3
STK_RES = 4


class StackBank(NamedTuple):
    """Per-slot DFS stacks held in device arrays ([S, D, ...]).

    This is the device-resident replacement for the host ``SegmentPool``
    row bookkeeping: one entry per live partial embedding, with the
    frontier/used/φ/depth lanes the wave programs consume plus the
    Lemma-4 resolution lanes (Γ accumulator, outstanding-children count,
    reported flag, parent entry index). ``pstack``/``ptop`` form the
    per-slot pending LIFO the expansion loop repacks waves from — the
    host never sees individual rows, only per-slot scalars.
    """
    frontier: jax.Array      # int32 [S, D, N_PAD]
    used: jax.Array          # uint32 [S, D, W]
    phi: jax.Array           # int32 [S, D, N_PAD + 1]
    depth: jax.Array         # int32 [S, D]
    cand: jax.Array          # uint32 [S, D, W] leftover bitmap (LEFT)
    state: jax.Array         # int8 [S, D] STK_* lifecycle
    gamma: jax.Array         # uint32 [S, D, MASK_WORDS] Γ* accumulator
    outstanding: jax.Array   # int32 [S, D] unresolved allocated children
    reported: jax.Array      # bool [S, D] subtree reached an embedding
    parent: jax.Array        # int32 [S, D] parent entry index (-1 = root)
    pstack: jax.Array        # int32 [S, D] pending LIFO of entry indices
    ptop: jax.Array          # int32 [S]

    @staticmethod
    def empty(n_slots: int, depth_cap: int, w: int) -> "StackBank":
        s, d = n_slots, depth_cap
        return StackBank(
            frontier=jnp.full((s, d, N_PAD), -1, jnp.int32),
            used=jnp.zeros((s, d, w), jnp.uint32),
            phi=jnp.zeros((s, d, N_PAD + 1), jnp.int32),
            depth=jnp.zeros((s, d), jnp.int32),
            cand=jnp.zeros((s, d, w), jnp.uint32),
            state=jnp.zeros((s, d), jnp.int8),
            gamma=jnp.zeros((s, d, MASK_WORDS), jnp.uint32),
            outstanding=jnp.zeros((s, d), jnp.int32),
            reported=jnp.zeros((s, d), bool),
            parent=jnp.full((s, d), -1, jnp.int32),
            pstack=jnp.zeros((s, d), jnp.int32),
            ptop=jnp.zeros((s,), jnp.int32))


@functools.partial(jax.jit, donate_argnums=(0,))
def clear_slot_stack(sb: StackBank, slot: jax.Array) -> StackBank:
    """Release every entry of one slot (query retired / evicted). Only
    the state and top-pointer lanes matter — FREE entries' payload lanes
    are rewritten on allocation."""
    return sb._replace(state=sb.state.at[slot].set(STK_FREE),
                       ptop=sb.ptop.at[slot].set(0))


@functools.partial(jax.jit, donate_argnums=(0,))
def clear_slot_stacks(sb: StackBank, slots: jax.Array) -> StackBank:
    """Batch variant of :func:`clear_slot_stack`: release several slots
    in one dispatch (``slots`` [k]; out-of-range values drop)."""
    return sb._replace(
        state=sb.state.at[slots].set(STK_FREE, mode="drop"),
        ptop=sb.ptop.at[slots].set(0, mode="drop"))


class DeviceResult(NamedTuple):
    """Per-slot scalar digest of one device-resident dispatch.

    This is everything that crosses the device→host boundary besides
    the embedding batch: counters for stats/budget accounting plus the
    stack's live/pending sizes for completion detection. No per-row
    lanes — the rows stayed on device.
    """
    tb: PatternStoreBank
    sb: StackBank
    d_accepted: jax.Array    # int32 [S] admitted root rows
    d_expanded: jax.Array    # int32 [S] rows expanded (selected)
    d_rows: jax.Array        # int32 [S] child rows allocated
    d_prunes: jax.Array      # int32 [S] Δ dead-end prunes
    d_inj: jax.Array         # int32 [S] injectivity kills
    d_stored: jax.Array      # int32 [S] patterns stored (L1 + L4)
    d_pending: jax.Array     # int32 [S] pending LIFO size after
    d_live: jax.Array        # int32 [S] non-FREE entries after
    d_outsum: jax.Array      # int32 [S] sum of live entries' outstanding
    d_childlive: jax.Array   # int32 [S] live entries with a parent
    pat_stored: jax.Array    # int32 [S] Δ insert counters
    pat_overwrites: jax.Array
    pat_evictions: jax.Array
    pat_dropped: jax.Array
    emb_frontier: jax.Array  # int32 [emb_cap, N_PAD]
    emb_slot: jax.Array      # int32 [emb_cap]
    n_emb: jax.Array         # int32
    n_ids: jax.Array         # int32 fresh embedding ids consumed


def _slot_counts(sel_slot: jax.Array, valid: jax.Array, n_slots: int,
                 weights: jax.Array | None = None) -> jax.Array:
    """Per-slot sum of ``weights`` (default 1) over valid rows."""
    tgt = jnp.where(valid, sel_slot, n_slots)
    w = (valid.astype(jnp.int32) if weights is None
         else jnp.where(valid, weights, 0))
    return jnp.zeros((n_slots + 1,), jnp.int32).at[tgt].add(w)[:n_slots]


def _group_rank(slot: jax.Array, valid: jax.Array, n_slots: int
                ) -> jax.Array:
    """Rank of each valid element within its slot group.

    Requires the valid elements to be grouped by slot in ascending
    order (wave rows and their flattened children are laid out that way
    by construction): rank = global running index minus the group's
    first global index, recovered with a scatter-min.
    """
    gidx = jnp.cumsum(valid.astype(jnp.int32)) - 1
    big = jnp.int32(2**30)
    start = jnp.full((n_slots + 1,), big, jnp.int32).at[
        jnp.where(valid, slot, n_slots)].min(gidx)
    return jnp.where(valid, gidx - start[slot], 0)


def _select_set_bits(mask: jax.Array, k: int) -> jax.Array:
    """Indices of the first ``k`` set bits of bool [n] ``mask``, in
    ascending order (``n`` where exhausted).

    Gather-based: binary search over the inclusive cumsum. The obvious
    scatter (``zeros(k).at[rank].set(iota)``) carries *n* updates, and
    XLA's CPU scatter executes updates serially — at stack-bank sizes
    (n = S·D ≈ 65k) one such compaction costs milliseconds, and the
    resolution sweep runs several per iteration.
    """
    csum = jnp.cumsum(mask.astype(jnp.int32))
    ks = jnp.arange(1, k + 1, dtype=jnp.int32)
    return jnp.searchsorted(csum, ks, side="left").astype(jnp.int32)


def _free_entry_order(isfree: jax.Array) -> jax.Array:
    """``eor[s, r]`` = entry id of the r-th free entry of slot ``s``
    (``d_cap`` when exhausted) — row-wise :func:`_select_set_bits`, for
    the same serial-scatter reason."""
    d_cap = isfree.shape[1]
    frank = jnp.cumsum(isfree.astype(jnp.int32), axis=1)
    ks = jnp.arange(1, d_cap + 1, dtype=jnp.int32)
    return jax.vmap(
        lambda row: jnp.searchsorted(row, ks, side="left"))(
            frank).astype(jnp.int32)


def _resolution_sweep(qb: QueryBank, tb: PatternStoreBank, lanes: dict,
                      learn_enabled: jax.Array, batch: int
                      ) -> tuple[PatternStoreBank, dict, jax.Array,
                                 StoreCounters]:
    """One Lemma-4 resolution pass over every slot's stack.

    Phase A folds *every* resolved (RES) child into its parent: Γ|=child
    Γ unless the child reported, outstanding -= child count, child
    entries freed (resolved roots are freed directly). outstanding and
    reported fold with conflict-free scatter add/max; the Γ OR-fold has
    no scatter-or primitive, so children are sorted by parent and
    OR-reduced with a segmented associative scan — a kpr-way fan-out
    folds in one sweep instead of kpr winner-per-parent sweeps, which
    kept the drain loop spinning for hundreds of iterations. Phase B
    finalizes up to ``batch`` subtree-exhausted WAIT entries
    (outstanding == 0, no pending leftover — LEFT is a distinct state):
    the μ==0-vs-μ>0 conversion of ``SegmentPool.finalize_row`` and the
    Δ store of ``queue_store`` become lanes, and the entry turns RES
    carrying the *converted* Γ for the next phase-A fold. One sweep per
    expansion iteration keeps resolution concurrent with the DFS;
    leftover unresolved state legally persists across dispatches.

    Returns (tb, lanes, per-slot stores int32 [S], insert counters).
    """
    state, gamma = lanes["state"], lanes["gamma"]
    outstanding, reported = lanes["outstanding"], lanes["reported"]
    s_dim, d_dim = state.shape
    s_grid = jnp.broadcast_to(jnp.arange(s_dim)[:, None], (s_dim, d_dim))

    # ---- phase A: fold resolved children into their parents ------------
    res_m = state == STK_RES
    par = lanes["parent"]
    res_child = res_m & (par >= 0)        # RES roots free directly
    n_flat = s_dim * d_dim
    # compact to the first 2*batch resolved children (one iteration
    # creates at most ``batch`` RES rows at expansion + ``batch`` at
    # phase-B finalize; stragglers legally wait a sweep) so the sort
    # below runs over O(batch), not the whole stack
    b_cap = 2 * batch
    child_i = _select_set_bits(res_child.reshape(-1), b_cap)
    valid_c = child_i < n_flat
    ci = child_i.clip(0, n_flat - 1)
    rep_flat = reported.reshape(-1)
    gam_flat = gamma.reshape(n_flat, -1)
    pgid_all = (s_grid * d_dim + par).reshape(-1)
    pg = jnp.where(valid_c, pgid_all[ci], n_flat)   # n_flat = dump row
    crep = rep_flat[ci]

    cnt = jnp.zeros((n_flat + 1,), jnp.int32).at[pg].add(1)[:n_flat]
    rep_fold = jnp.zeros((n_flat + 1,), bool).at[pg].max(crep)[:n_flat]

    # a RES parent never has RES children (it finalized with
    # outstanding == 0), so the per-parent OR below is race-free: sort
    # the taken children by parent and OR-reduce with a segmented scan —
    # there is no scatter-or primitive, and winner-per-parent sweeps
    # made a kpr-way fan-out take kpr drain iterations
    order = jnp.argsort(pg)
    ps = pg[order]
    gs = jnp.where((valid_c & ~crep)[order, None],
                   gam_flat[ci[order]], 0)  # a reported child folds no Γ

    def _seg_or(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb[..., None], vb, va | vb)

    seg_new = jnp.concatenate(
        [jnp.ones((1,), bool), ps[1:] != ps[:-1]])
    _, acc = lax.associative_scan(_seg_or, (seg_new, gs))
    is_end = jnp.concatenate(
        [ps[1:] != ps[:-1], jnp.ones((1,), bool)]) & (ps < n_flat)
    # segment ends carry the full OR and hit unique parents
    contrib = jnp.zeros((n_flat + 1, gamma.shape[-1]), gamma.dtype).at[
        jnp.where(is_end, ps, n_flat)].set(acc)[:n_flat]
    gamma = (gam_flat | contrib).reshape(gamma.shape)
    reported = rep_flat.reshape(s_dim, d_dim) | rep_fold.reshape(
        s_dim, d_dim)
    outstanding = outstanding - cnt.reshape(s_dim, d_dim)
    state_flat = state.reshape(-1).at[child_i].set(
        jnp.int8(STK_FREE), mode="drop")      # folded children freed
    state = jnp.where(res_m & (par < 0), jnp.int8(STK_FREE),
                      state_flat.reshape(s_dim, d_dim))

    # ---- phase B: finalize subtree-exhausted WAIT entries --------------
    fin = (state == STK_WAIT) & (outstanding == 0)
    bsel = _select_set_bits(fin.reshape(-1), batch)
    valid_b = bsel < n_flat
    bclip = bsel.clip(0, n_flat - 1)
    slot_b = jnp.where(valid_b, bclip // d_dim, 0)
    ent_b = jnp.where(valid_b, bclip % d_dim, 0)

    d_b = lanes["depth"][slot_b, ent_b]
    gm = gamma[slot_b, ent_b]                       # [B, MW]
    rep_b = reported[slot_b, ent_b]
    fr_b = lanes["frontier"][slot_b, ent_b]
    ph_b = lanes["phi"][slot_b, ent_b]
    # finalize_row's conversion: Γ mentions position d → the row's own
    # Eq. 2 neighbourhood joins and everything >= d is cut
    qnbr_b = _pack_mask_rows(qb.nbr_mask[slot_b, d_b])
    has_bit = ((gm & _position_bits(d_b)) != 0).any(axis=1)
    gconv = jnp.where(has_bit[:, None],
                      (gm | qnbr_b) & _below_bits_rows(d_b), gm)
    key_pos = (d_b - 1).clip(0)
    key_v = jnp.take_along_axis(fr_b, key_pos[:, None], axis=1)[:, 0]
    mu = _mask_bitlen(gconv & _below_bits_rows(key_pos))
    phi_v = jnp.take_along_axis(ph_b, mu[:, None], axis=1)[:, 0]
    do_store = (valid_b & ~rep_b & (d_b >= 1)
                & qb.learn[slot_b] & learn_enabled)
    tb, pat_c = store_patterns_mq(tb, slot_b, key_pos, key_v, phi_v, mu,
                                  gconv, do_store)

    state = state.reshape(-1).at[bsel].set(
        jnp.int8(STK_RES), mode="drop").reshape(s_dim, d_dim)
    sb_eff = jnp.where(valid_b, slot_b, s_dim)
    gamma = gamma.at[sb_eff, ent_b].set(gconv, mode="drop")

    stores = _slot_counts(slot_b, do_store, s_dim)
    lanes = dict(lanes, state=state, gamma=gamma, outstanding=outstanding,
                 reported=reported)
    return tb, lanes, stores, pat_c


@functools.partial(jax.jit, donate_argnums=(2, 3), static_argnames=(
    "kpr", "emb_cap", "backend", "wave", "block_f", "dma_depth"))
def run_device_megastep(g: GraphArrays, qb: QueryBank,
                        tb: PatternStoreBank, sb: StackBank,
                        in_root: jax.Array, in_rid: jax.Array,
                        in_slot: jax.Array, in_valid: jax.Array,
                        active: jax.Array, id_base: jax.Array,
                        learn_enabled: jax.Array, t_max: jax.Array,
                        kpr: int = 8, emb_cap: int = 512,
                        backend: str = "jnp",
                        wave: int | None = None,
                        block_f: int = 8,
                        dma_depth: int | None = None) -> DeviceResult:
    """One dispatch of the device-resident scheduler loop.

    Admits root rows into free stack entries, then runs up to ``t_max``
    repack→expand→resolve iterations entirely on device: each iteration
    pops a mixed wave of pending entries off the per-slot LIFOs (DFS
    order, waterfill quota across slots), expands it (Eq. 2 refinement,
    injectivity, top-kpr extraction, Eq. 7 dead-end probe — fresh
    entries — or re-extraction from the stored leftover bitmap — LEFT
    entries), allocates surviving non-last children as new stack
    entries, emits last-level children into the embedding buffer, stores
    Lemma-1 patterns in-loop, and runs one Lemma-4 resolution sweep.
    A final progress-bounded drain resolves what the iterations left.

    Children that find no free entry fold back into their parent's
    leftover bitmap (the entry re-queues as LEFT), so a full stack
    degrades to throttling, never to lost work. ``t_max`` is traced —
    the adaptive scheduler drops it to 1 under high prune rates without
    recompiling. Only this digest (per-slot scalars + the embedding
    batch) crosses back to the host.
    """
    # the root-intake width ``r`` is decoupled from the wave width
    # ``f``: a dispatch can land more roots than one wave expands, so a
    # fresh query batch reaches the device in one call instead of
    # trickling across several fixed-cost dispatches
    r = in_root.shape[0]
    f = wave if wave is not None else r
    n_slots, d_cap = sb.state.shape
    w = sb.used.shape[2]
    assert emb_cap >= f * kpr, "emb buffer cannot hold one iteration"
    f_rows = jnp.arange(f)
    # per-iteration allocation bound: overflow children fold back into
    # their parent's leftover bitmap, so this only throttles, and it
    # keeps the per-row lane-scatter cost off the f·kpr padding
    a_cap = min(8 * f, f * kpr)

    lanes = dict(frontier=sb.frontier, used=sb.used, phi=sb.phi,
                 depth=sb.depth, cand=sb.cand, state=sb.state,
                 gamma=sb.gamma, outstanding=sb.outstanding,
                 reported=sb.reported, parent=sb.parent,
                 pstack=sb.pstack, ptop=sb.ptop)

    # ---- root admission: place accepted inputs into free entries -------
    # Inputs are grouped by slot. Acceptance is throttled so a dispatch
    # leaves allocation headroom; unaccepted roots stay queued on the
    # host (the cursor only advances by d_accepted).
    with jax.named_scope(spans.MEGA_ROOTS):
        isfree = lanes["state"] == STK_FREE
        free_n = isfree.sum(axis=1).astype(jnp.int32)
        n_in = _slot_counts(in_slot, in_valid, n_slots)
        accept_s = jnp.where(active, jnp.minimum(n_in, free_n // (kpr + 2)), 0)
        rank_in = _group_rank(in_slot, in_valid, n_slots)
        acc = in_valid & (rank_in < accept_s[in_slot])

        eor = _free_entry_order(isfree)
        ent_in = eor[in_slot, rank_in.clip(0, d_cap - 1)]
        ok_in = acc & (ent_in < d_cap)
        tgt_s = jnp.where(ok_in, in_slot, n_slots)
        tgt_e = jnp.where(ok_in, ent_in, 0)

        root_f = jnp.where(jnp.arange(N_PAD)[None, :] == 0,
                           in_root[:, None], -1).astype(jnp.int32)
        rv = in_root.clip(0)
        root_u = jnp.zeros((r, w), jnp.uint32).at[
            jnp.arange(r), (rv // 32)].set(jnp.uint32(1) << (rv % 32).astype(
                jnp.uint32))
        root_p = jnp.where(jnp.arange(N_PAD + 1)[None, :] == 1,
                           in_rid[:, None], 0).astype(jnp.int32)

        lanes["frontier"] = lanes["frontier"].at[tgt_s, tgt_e].set(
            root_f, mode="drop")
        lanes["used"] = lanes["used"].at[tgt_s, tgt_e].set(root_u, mode="drop")
        lanes["phi"] = lanes["phi"].at[tgt_s, tgt_e].set(root_p, mode="drop")
        lanes["depth"] = lanes["depth"].at[tgt_s, tgt_e].set(1, mode="drop")
        lanes["state"] = lanes["state"].at[tgt_s, tgt_e].set(
            jnp.int8(STK_FRESH), mode="drop")
        lanes["gamma"] = lanes["gamma"].at[tgt_s, tgt_e].set(
            jnp.uint32(0), mode="drop")
        lanes["outstanding"] = lanes["outstanding"].at[tgt_s, tgt_e].set(
            0, mode="drop")
        lanes["reported"] = lanes["reported"].at[tgt_s, tgt_e].set(
            False, mode="drop")
        lanes["parent"] = lanes["parent"].at[tgt_s, tgt_e].set(-1, mode="drop")
        lanes["cand"] = lanes["cand"].at[tgt_s, tgt_e].set(
            jnp.uint32(0), mode="drop")
        push_pos = jnp.where(ok_in, lanes["ptop"][in_slot] + rank_in, 0)
        lanes["pstack"] = lanes["pstack"].at[tgt_s, push_pos].set(
            ent_in, mode="drop")
        d_accepted = _slot_counts(in_slot, ok_in, n_slots)
        lanes["ptop"] = lanes["ptop"] + d_accepted

        zs = jnp.zeros((n_slots,), jnp.int32)
        carry = dict(
            tb=tb, it=jnp.int32(0),
            emb_frontier=jnp.full((emb_cap, N_PAD), -1, jnp.int32),
            emb_slot=jnp.zeros((emb_cap,), jnp.int32), n_emb=jnp.int32(0),
            id_ctr=jnp.asarray(id_base, jnp.int32),
            pat=StoreCounters.zeros(n_slots),
            d_expanded=zs, d_rows=zs, d_prunes=zs, d_inj=zs, d_stored=zs,
            **lanes)

    lane_keys = tuple(lanes.keys())

    def cond(s):
        return ((s["it"] < t_max)
                & (jnp.where(active, s["ptop"], 0) > 0).any()
                & (s["n_emb"] + f * kpr <= emb_cap))

    def body(s):
        st, ptop = s["state"], s["ptop"]

        # ---- wave selection: waterfill quota over pending slots --------
        with jax.named_scope(spans.MEGA_SELECT):
            pend = jnp.where(active, ptop, 0)
            free_now = (st == STK_FREE).sum(axis=1).astype(jnp.int32)
            quota_cap = jnp.maximum(free_now // (kpr + 1), 1)
            desire = jnp.minimum(pend, quota_cap)
            n_act = jnp.maximum((desire > 0).sum(), 1)
            base = jnp.int32(f) // n_act
            q1 = jnp.minimum(desire, base)
            want = desire - q1
            rem = jnp.int32(f) - q1.sum()
            extra = jnp.clip(jnp.minimum(
                want, rem - (jnp.cumsum(want) - want)), 0, None)
            q = q1 + extra                                       # [S]
            offs = jnp.cumsum(q) - q
            total = q.sum()
            s_of = jnp.searchsorted(jnp.cumsum(q), f_rows,
                                    side="right").astype(jnp.int32)
            row_valid = f_rows < total
            s_of_c = jnp.where(row_valid, s_of, 0).clip(0, n_slots - 1)
            k_in = (f_rows - offs[s_of_c]).clip(0)
            ent_sel = s["pstack"][s_of_c, (ptop[s_of_c] - 1 - k_in).clip(0)]
            e_c = jnp.where(row_valid, ent_sel, 0)
            ptop2 = ptop - q

            wf = s["frontier"][s_of_c, e_c]
            wu = s["used"][s_of_c, e_c]
            wphi = s["phi"][s_of_c, e_c]
            wd = s["depth"][s_of_c, e_c]
            wcand = s["cand"][s_of_c, e_c]
            wg = s["gamma"][s_of_c, e_c]
            st_sel = st[s_of_c, e_c]
            is_left = (st_sel == STK_LEFT) & row_valid
            is_fresh = (st_sel == STK_FRESH) & row_valid

        # ---- expansion (fresh: full Eq.2 pass; LEFT: re-extraction) ----
        with jax.named_scope(spans.MEGA_REFINE):
            refined = refine_eq2_mq(g, qb, s_of_c, wf, wd, backend, block_f,
                                    dma_depth)
            refined = jnp.where(is_fresh[:, None], refined, jnp.uint32(0))
            refined_empty = is_fresh & (_popcount_rows(refined) == 0)

        with jax.named_scope(spans.MEGA_INJECT):
            inj_words = refined & wu
            n_inj_row = jnp.where(is_fresh, _popcount_rows(inj_words), 0)
            depth_bits = _position_bits(wd)

            # vectorized over positions (no fori_loop): position bits are
            # disjoint across p, so the OR-fold is an exact integer sum
            verts = wf.clip(0)                                   # [F, NP]
            words = jnp.take_along_axis(refined, verts // 32, axis=1)
            hit = ((words >> (verts % 32).astype(jnp.uint32)) & 1) > 0
            hit &= (jnp.arange(N_PAD)[None, :] < wd[:, None]) \
                & is_fresh[:, None]
            posb = _position_bits(jnp.arange(N_PAD, dtype=jnp.int32))
            inj_mask = (hit[:, :, None].astype(jnp.uint32)
                        * posb[None, :, :]).sum(axis=1, dtype=jnp.uint32)
            inj_mask = inj_mask | jnp.where(hit.any(axis=1)[:, None],
                                            depth_bits, jnp.uint32(0))

        with jax.named_scope(spans.MEGA_EXTRACT):
            live = jnp.where(is_left[:, None], wcand, refined & ~wu)
            child_v, leftover, n_leftover = _extract_topk_packed(live, kpr)
        with jax.named_scope(spans.MEGA_PROBE):
            prune, prune_mask, tb_l = deadend_lookup_children_mq(
                s["tb"], wphi, s_of_c, wd, child_v)
            child_valid = (child_v >= 0) & ~prune & row_valid[:, None]
            partial = jnp.where(is_left[:, None], prune_mask,
                                inj_mask | prune_mask)
            n_pruned_row = jnp.where(row_valid, prune.sum(axis=1), 0)

        with jax.named_scope(spans.MEGA_EXTRACT):
            # ---- materialize children (flat [F*kpr], slot-grouped) ---------
            parent_local = jnp.repeat(jnp.arange(f, dtype=jnp.int32), kpr)
            flat_v = child_v.reshape(-1)
            cvalid_flat = child_valid.reshape(-1)
            d_par = wd[parent_local]
            slot_flat = s_of_c[parent_local]
            is_last = wd + 1 == qb.n_query[s_of_c]
            last_flat = is_last[parent_local]
            pos = jnp.arange(N_PAD)
            cf2 = wf[parent_local]
            cf2 = jnp.where((pos[None, :] == d_par[:, None])
                            & cvalid_flat[:, None], flat_v[:, None], cf2)
            vv = flat_v.clip(0)

            # ---- embeddings: last-level children, no allocation ------------
            emb_valid = cvalid_flat & last_flat
            emb_off = jnp.cumsum(emb_valid.astype(jnp.int32)) - 1
            emb_idx = jnp.where(emb_valid, s["n_emb"] + emb_off, emb_cap)
            emb_frontier = s["emb_frontier"].at[emb_idx].set(cf2, mode="drop")
            emb_slot = s["emb_slot"].at[emb_idx].set(slot_flat, mode="drop")
            n_emb_new = emb_valid.sum().astype(jnp.int32)
            n_emb_row = (child_valid & is_last[:, None]).sum(
                axis=1).astype(jnp.int32)

        with jax.named_scope(spans.MEGA_ALLOC):
            # ---- allocate non-last children into free entries --------------
            # compacted to at most ``a_cap`` rows: the CPU backend executes
            # scatter updates serially, so every lane scatter below costs
            # per-row — and most of the F·kpr child rows are dead padding.
            # Children past the cap simply fold back into their parent's
            # leftover bitmap (LEFT requeue), the same sound degradation as
            # running out of free entries.
            eor_l = _free_entry_order(st == STK_FREE)
            app_valid = cvalid_flat & ~last_flat
            a_sel = _select_set_bits(app_valid, a_cap)           # [A]
            a_valid = a_sel < f * kpr
            a_i = a_sel.clip(0, f * kpr - 1)
            slot_a = slot_flat[a_i]
            par_a = parent_local[a_i]
            j = _group_rank(slot_a, a_valid, n_slots)
            ent_ch = eor_l[slot_a, j.clip(0, d_cap - 1)]
            ok = a_valid & (ent_ch < d_cap)
            alloc_flag = jnp.zeros((f * kpr,), bool).at[
                jnp.where(ok, a_sel, f * kpr)].set(True, mode="drop")
            fail = app_valid & ~alloc_flag

            # children that found no entry fold back into the parent row's
            # leftover bitmap (distinct vertices → add == or)
            fold = jnp.zeros((f, w), jnp.uint32).at[
                parent_local, (vv // 32)].add(
                    jnp.where(fail,
                              jnp.uint32(1) << (vv % 32).astype(jnp.uint32),
                              jnp.uint32(0)))
            leftover = leftover | fold
            n_leftover = _popcount_rows(leftover)

            ok_s = jnp.where(ok, slot_a, n_slots)
            ok_e = jnp.where(ok, ent_ch, 0)
            child_ids = s["id_ctr"] + jnp.cumsum(ok.astype(jnp.int32)) - 1
            d_par_a = d_par[a_i]
            vv_a = vv[a_i]
            cf_a = cf2[a_i]
            cu_a = wu[par_a] | jnp.zeros((a_cap, w), jnp.uint32).at[
                jnp.arange(a_cap), (vv_a // 32)].set(
                    jnp.uint32(1) << (vv_a % 32).astype(jnp.uint32))
            pos_phi = jnp.arange(N_PAD + 1)
            cp_a = wphi[par_a]
            cp_a = jnp.where((pos_phi[None, :] == d_par_a[:, None] + 1)
                             & ok[:, None], child_ids[:, None], cp_a)
            n_alloc = ok.sum().astype(jnp.int32)
            n_alloc_row = alloc_flag.reshape(f, kpr).sum(
                axis=1).astype(jnp.int32)
            alloc_s = _slot_counts(slot_a, ok, n_slots)

            fr_l = s["frontier"].at[ok_s, ok_e].set(cf_a, mode="drop")
            us_l = s["used"].at[ok_s, ok_e].set(cu_a, mode="drop")
            ph_l = s["phi"].at[ok_s, ok_e].set(cp_a, mode="drop")
            de_l = s["depth"].at[ok_s, ok_e].set(d_par_a + 1, mode="drop")
            st_l = st.at[ok_s, ok_e].set(jnp.int8(STK_FRESH), mode="drop")
            gm_l = s["gamma"].at[ok_s, ok_e].set(jnp.uint32(0), mode="drop")
            ou_l = s["outstanding"].at[ok_s, ok_e].set(0, mode="drop")
            re_l = s["reported"].at[ok_s, ok_e].set(False, mode="drop")
            pa_l = s["parent"].at[ok_s, ok_e].set(
                ent_sel[par_a], mode="drop")
            ca_l = s["cand"].at[ok_s, ok_e].set(jnp.uint32(0), mode="drop")

        with jax.named_scope(spans.MEGA_STORE):
            # ---- in-loop Lemma-1 stores (Eq. 2 came back empty) ------------
            do_store = (refined_empty & (wd >= 1)
                        & qb.learn[s_of_c] & learn_enabled)
            qnbr = _pack_mask_rows(qb.nbr_mask[s_of_c, wd])
            gamma_w = qnbr & _below_bits_rows(wd)
            key_pos = (wd - 1).clip(0)
            key_v = jnp.take_along_axis(wf, key_pos[:, None], axis=1)[:, 0]
            mu = _mask_bitlen(gamma_w & _below_bits_rows(key_pos))
            phi_id = jnp.take_along_axis(wphi, mu[:, None], axis=1)[:, 0]
            tb2, pat_c = store_patterns_mq(tb_l, s_of_c, key_pos, key_v,
                                           phi_id, mu, gamma_w, do_store)

        with jax.named_scope(spans.MEGA_ALLOC):
            # ---- update the selected entries -------------------------------
            has_left = (n_leftover > 0) & row_valid & ~refined_empty
            new_state = jnp.where(
                refined_empty, jnp.int8(STK_RES),
                jnp.where(has_left, jnp.int8(STK_LEFT), jnp.int8(STK_WAIT)))
            new_g = (wg | partial
                     | jnp.where(refined_empty[:, None], gamma_w,
                                 jnp.uint32(0)))
            sel_s = jnp.where(row_valid, s_of_c, n_slots)
            st_l = st_l.at[sel_s, e_c].set(new_state, mode="drop")
            gm_l = gm_l.at[sel_s, e_c].set(new_g, mode="drop")
            ou_l = ou_l.at[sel_s, e_c].set(
                s["outstanding"][s_of_c, e_c] + n_alloc_row, mode="drop")
            re_l = re_l.at[sel_s, e_c].set(
                s["reported"][s_of_c, e_c] | (n_emb_row > 0), mode="drop")
            ca_l = ca_l.at[sel_s, e_c].set(
                jnp.where(has_left[:, None], leftover, jnp.uint32(0)),
                mode="drop")

            # ---- re-queue: LEFT entries below, fresh children on top -------
            lrank = _group_rank(s_of_c, has_left, n_slots)
            lpos = jnp.where(has_left, ptop2[s_of_c] + lrank, 0)
            ps_l = s["pstack"].at[
                jnp.where(has_left, s_of_c, n_slots), lpos].set(
                    ent_sel, mode="drop")
            n_left_s = _slot_counts(s_of_c, has_left, n_slots)
            ptop3 = ptop2 + n_left_s
            cpos = jnp.where(ok, ptop3[slot_a] + j, 0)
            ps_l = ps_l.at[jnp.where(ok, slot_a, n_slots), cpos].set(
                ent_ch, mode="drop")
            ptop4 = ptop3 + alloc_s

            new_lanes = dict(
                frontier=fr_l, used=us_l, phi=ph_l, depth=de_l, cand=ca_l,
                state=st_l, gamma=gm_l, outstanding=ou_l, reported=re_l,
                parent=pa_l, pstack=ps_l, ptop=ptop4)

        # ---- one resolution sweep per iteration ------------------------
        with jax.named_scope(spans.MEGA_RESOLVE):
            tb3, new_lanes, n_stored_fin, pat_f = _resolution_sweep(
                qb, tb2, new_lanes, learn_enabled, f)

        return dict(
            tb=tb3, it=s["it"] + 1,
            emb_frontier=emb_frontier, emb_slot=emb_slot,
            n_emb=s["n_emb"] + n_emb_new, id_ctr=s["id_ctr"] + n_alloc,
            pat=s["pat"].add(pat_c).add(pat_f),
            d_expanded=s["d_expanded"] + _slot_counts(
                s_of_c, row_valid, n_slots),
            d_rows=s["d_rows"] + alloc_s,
            d_prunes=s["d_prunes"] + _slot_counts(
                s_of_c, row_valid, n_slots, n_pruned_row),
            d_inj=s["d_inj"] + _slot_counts(
                s_of_c, row_valid, n_slots, n_inj_row),
            d_stored=s["d_stored"] + n_stored_fin + _slot_counts(
                s_of_c, do_store, n_slots),
            **new_lanes)

    with jax.named_scope(spans.MEGA_SELECT):
        s = lax.while_loop(cond, body, carry)

    # ---- final drain: a few more resolution sweeps ---------------------
    # Bounded by a small constant, not run to quiescence: each sweep
    # costs real time even when nearly empty, and unresolved WAIT/RES
    # state legally persists across dispatches — the next dispatch's
    # in-loop sweeps (or its own drain) continue the fold, and trailing
    # resolution-only dispatches are cheap because the expansion loop
    # exits immediately with nothing pending.
    with jax.named_scope(spans.MEGA_DRAIN):
        def drain_cond(d):
            can_fold = (d["state"] == STK_RES).any()
            can_fin = ((d["state"] == STK_WAIT)
                       & (d["outstanding"] == 0)).any()
            return (can_fold | can_fin) & (d["it"] < 12)

        def drain_body(d):
            lanes_d = {k: d[k] for k in lane_keys}
            tb_d, lanes_d, n_st, pat_d = _resolution_sweep(
                qb, d["tb"], lanes_d, learn_enabled, f)
            return dict(d, tb=tb_d, it=d["it"] + 1,
                        d_stored=d["d_stored"] + n_st,
                        pat=d["pat"].add(pat_d), **lanes_d)

        s = lax.while_loop(drain_cond, drain_body,
                           dict(s, it=jnp.int32(0)))

        sb_out = StackBank(**{k: s[k] for k in lane_keys})
        live_mask = s["state"] != STK_FREE
        live = live_mask.sum(axis=1).astype(jnp.int32)
        # Lemma-4 conservation lanes for the host-side digest validator:
        # every live non-root entry is counted exactly once in its parent's
        # outstanding counter, so per slot
        #   sum(outstanding over live) == count(live with parent >= 0)
        d_outsum = jnp.where(live_mask, s["outstanding"], 0) \
            .sum(axis=1).astype(jnp.int32)
        d_childlive = (live_mask & (s["parent"] >= 0)) \
            .sum(axis=1).astype(jnp.int32)
        return DeviceResult(
            tb=s["tb"], sb=sb_out,
            d_accepted=d_accepted, d_expanded=s["d_expanded"],
            d_rows=s["d_rows"], d_prunes=s["d_prunes"], d_inj=s["d_inj"],
            d_stored=s["d_stored"], d_pending=s["ptop"], d_live=live,
            d_outsum=d_outsum, d_childlive=d_childlive,
            pat_stored=s["pat"].stored, pat_overwrites=s["pat"].overwrites,
            pat_evictions=s["pat"].evictions, pat_dropped=s["pat"].dropped,
            emb_frontier=s["emb_frontier"], emb_slot=s["emb_slot"],
            n_emb=s["n_emb"],
            n_ids=s["id_ctr"] - jnp.asarray(id_base, jnp.int32))


# (the old single-query S == 1 wrappers — expand_wave &c. — are gone:
# nothing called them anymore, and every sequential-style caller goes
# through the 1-slot WaveEngine facade instead)
