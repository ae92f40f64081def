"""Pallas TPU kernel: Eq. 2 candidate refinement over packed bitmaps.

The matcher's hot loop. For every partial embedding (frontier row) the
refined candidate set of the next query position is

    refined[i] = cand[i] ∧ ⋀_{p active for row i} adj[frontier[i, p]]

an AND-reduction over dynamically gathered adjacency bitmap rows. Since
the multi-query engine refactor the candidate row and the active-position
set are *per row* (each wave row may belong to a different query at a
different depth), so the kernel takes ``cand [F, W]`` and
``active [F, NP]`` — the single-query entry point broadcasts.

Block geometry: the grid is one step per ``(BLOCK_F, W_pad)`` row block
and the position loop is folded *inside* the kernel body. Per grid step
the body runs ``fori_loop`` over positions and gathers one adjacency row
per sublane with a dynamic ``pl.ds`` load. The wrapper folds ``active``
into the frontier (inactive positions become -1) and the kernel reads
that ``[BLOCK_F, NP]`` block from SMEM, one block per grid step, so the
scalar memory it needs is independent of the wave width F (a whole
``[F, NP]`` scalar-prefetch operand overflows the v5e's 1 MiB SMEM at
F=1024). The adjacency bitmap is one whole-array VMEM block, single
buffered (its block index never changes, so a second pipeline buffer
would only double its footprint): V=16,384 vertices is 32 MiB of the
v5e's 128 MiB VMEM. ``W_pad`` is padded to a multiple of 128 lanes,
``F`` to a multiple of ``BLOCK_F`` sublanes. All words are int32
(bitwise ops are sign-agnostic; uint32<->int32 is a bitcast at the
wrapper).

Past the dense threshold (``kernels.config.HBM_ADJACENCY_MIN_VERTICES``)
the adjacency stays out of VMEM and this file carries the HBM-resident
variant :func:`refine_bitmap_rows_hier` over the two-level layout
(core.graph.HierBitmap, DESIGN.md §2): the chunk store stays in
``pl.ANY`` (compiler-placed, HBM at scale), the wrapper intersects
per-row chunk summaries into a live mask, and the kernel walks only
live chunks, copying each one into SMEM with ``make_async_copy``
(``dma_depth`` copies in flight) before AND-folding its words into the
output row. VMEM holds only the candidate, mask and output row blocks —
no adjacency. ``kernels/config.py`` owns the dense/hier threshold
(``use_hbm_adjacency``) plus the ``chunk_words``/``dma_depth`` knob
resolution.

Backend selection lives in ``kernels/config.py`` — ``interpret=None``
resolves from the process-wide config, so TPU runs cannot silently fall
into interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import interpret_mode, kernel_block_f, kernel_dma_depth

BLOCK_F = 8     # default sublanes per grid step (int32 min tile height)
                # — the tuned value resolves through kernels.config
LANES = 128     # TPU vector lane width: the hier kernel's row layout
HIER_ROWS = 8   # wave rows per grid step of the hier kernel


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _make_refine_kernel(block_f: int):
    """Kernel body closure over the (tuned) row-block height — the
    sublane loop is a static unroll, so the height is a trace-time
    constant, not a ref shape."""

    def _refine_kernel(nbr_ref, adj_ref, cand_ref, out_ref):
        np_ = nbr_ref.shape[1]

        def body(p, acc):
            rows = []
            for i in range(block_f):        # static unroll over sublanes
                vtx = nbr_ref[i, p]         # -1: position inactive
                row = adj_ref[pl.ds(jnp.maximum(vtx, 0), 1), :]
                rows.append(jnp.where(vtx >= 0, row, jnp.int32(-1)))
            return acc & jnp.concatenate(rows, axis=0)

        out_ref[...] = lax.fori_loop(0, np_, body, cand_ref[...])

    return _refine_kernel


@functools.partial(jax.jit, static_argnames=("interpret", "block_f"))
def _refine_rows_call(adj, cand, nbr, interpret: bool, block_f: int):
    v_pad, w_pad = adj.shape
    f_pad, np_ = nbr.shape
    return pl.pallas_call(
        _make_refine_kernel(block_f),
        grid=(f_pad // block_f,),
        in_specs=[
            pl.BlockSpec((block_f, np_), lambda i: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((v_pad, w_pad), lambda i: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
            pl.BlockSpec((block_f, w_pad), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_f, w_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((f_pad, w_pad), jnp.int32),
        interpret=interpret,
    )(nbr, adj, cand)


def refine_bitmap_rows(adj_bitmap: jax.Array, cand_rows: jax.Array,
                       frontier: jax.Array, active: jax.Array,
                       interpret: bool | None = None,
                       block_f: int | None = None) -> jax.Array:
    """Pallas-backed Eq. 2 refinement with per-row candidates.

    Args:
      adj_bitmap: int32/uint32 [V, W] packed adjacency rows.
      cand_rows:  int32/uint32 [F, W] packed candidates, one per row.
      frontier:   int32 [F, NP] mapped vertex per position (-1 unmapped).
      active:     bool/int32 [F, NP] mapped-neighbor positions, per row.
      interpret:  None resolves from ``kernels.config`` (the process-wide
                  backend); pass a bool to force.
      block_f:    rows per grid step. None resolves through the tuning
                  layer (scope override > tuning cache > default 8,
                  DESIGN.md §9). The compiled backend needs a multiple
                  of 8 (int32 sublane tile); interpret mode takes any
                  height >= 1.

    Returns int32 [F, W_pad >= W] refined packed bitmaps (caller slices
    the first W words).
    """
    if interpret is None:
        interpret = interpret_mode(None)
    v, w = adj_bitmap.shape
    if block_f is None:
        block_f = kernel_block_f(n_vertices=v)
    block_f = max(1, int(block_f))
    f, np_ = frontier.shape
    w_pad = max(LANES, _round_up(w, LANES))
    v_pad = _round_up(v, 8)
    f_pad = _round_up(max(f, 1), block_f)
    adj = jnp.zeros((v_pad, w_pad), jnp.int32).at[:v, :w].set(
        adj_bitmap.astype(jnp.int32))
    cand = jnp.zeros((f_pad, w_pad), jnp.int32).at[:f, :w].set(
        cand_rows.astype(jnp.int32))
    fr = frontier.astype(jnp.int32)
    nbr = jnp.where((active != 0) & (fr >= 0), fr.clip(0, v - 1), -1)
    nbr = jnp.full((f_pad, np_), -1, jnp.int32).at[:f].set(nbr)
    return _refine_rows_call(adj, cand, nbr, interpret, block_f)[:f]


def refine_bitmap(adj_bitmap: jax.Array, cand_row: jax.Array,
                  frontier: jax.Array, active: jax.Array,
                  interpret: bool | None = None,
                  block_f: int | None = None) -> jax.Array:
    """Single-query entry point: one shared candidate row and one shared
    active-position vector, broadcast over all F rows (the historical
    signature, kept for ``ops.refine_bitmap_op`` and the dry-run)."""
    f = frontier.shape[0]
    cand_rows = jnp.broadcast_to(
        cand_row.astype(jnp.int32)[None, :], (f, cand_row.shape[0]))
    act = jnp.broadcast_to(
        active.astype(jnp.int32)[None, :], (f, active.shape[0]))
    return refine_bitmap_rows(adj_bitmap, cand_rows, frontier, act,
                              interpret=interpret, block_f=block_f)


# --------------------------------------------------------------------------
# HBM-resident hierarchical variant (two-level layout, DESIGN.md §2)
# --------------------------------------------------------------------------

def summary_intersect(summary: jax.Array, cand_rows: jax.Array,
                      frontier: jax.Array, active: jax.Array,
                      chunk_words: int, w_pad: int
                      ) -> tuple[jax.Array, jax.Array]:
    """The first level of the hierarchical refinement, in plain jnp:
    ``sacc[i] = cand_summary[i] ∧ ⋀_{p active} summary[frontier[i, p]]``
    plus its expansion to a ``[F, w_pad]`` word mask.

    Summaries are O(V/32C) words per row, so this stays cheap enough to
    fold outside the kernel; a chunk dead in ``sacc`` is provably zero
    in the dense result (the candidate chunk was empty, or some active
    row misses it), which is what licenses the kernel to never read it.
    Returns ``(sacc int32 [F, SW], mask int32 [F, w_pad])``.
    """
    f, np_ = frontier.shape
    w = cand_rows.shape[1]
    c = int(chunk_words)
    sw = summary.shape[1]
    ncp = sw * 32
    shifts = jnp.arange(32, dtype=jnp.uint32)
    cand = cand_rows.astype(jnp.uint32)
    cpad = jnp.zeros((f, ncp * c), jnp.uint32).at[:, :w].set(cand)
    nonzero = (cpad.reshape(f, ncp, c) != 0).any(axis=2)
    cand_sum = (nonzero.reshape(f, sw, 32).astype(jnp.uint32)
                << shifts).sum(axis=2, dtype=jnp.uint32)

    def sbody(p, s):
        act = (active[:, p] != 0) & (frontier[:, p] >= 0)
        rows = summary.astype(jnp.uint32)[frontier[:, p].clip(0)]
        return jnp.where(act[:, None], s & rows, s)

    sacc = lax.fori_loop(0, np_, sbody, cand_sum)
    livebit = ((sacc[:, :, None] >> shifts) & jnp.uint32(1))
    mask = jnp.repeat(livebit.reshape(f, ncp), c, axis=1)
    mask = jnp.zeros((f, w_pad), jnp.uint32).at[:, :min(ncp * c, w_pad)] \
        .set(mask[:, :w_pad] * jnp.uint32(0xFFFFFFFF))
    return sacc.astype(jnp.int32), mask.astype(jnp.int32)


def _lane_rows(x: jax.Array) -> jax.Array:
    """Flatten ``x`` into int32 ``[R, 128]`` rows (zero tail): the only
    HBM layout from which the kernel may copy a slice into SMEM — a copy
    must span whole 128-lane rows."""
    flat = x.astype(jnp.int32).reshape(-1)
    pad = _round_up(flat.shape[0], LANES) - flat.shape[0]
    return jnp.pad(flat, (0, pad)).reshape(-1, LANES)


def _make_refine_hier_kernel(chunk_words: int, depth: int, rows: int):
    """Kernel body closure over the layout's static geometry:
    ``chunk_words`` (C), the DMA pipeline ``depth`` and the wave
    ``rows`` per grid step.

    Wave rows are laid out ``[G, 128]`` (G = W_pad/128 lane groups), so
    a chunk of C <= 128 words (a power of two) lies inside one lane
    group: the fold reads and writes that group with a dynamic *sublane*
    index and places the chunk's words with lane selects — the TPU
    lowering refuses dynamic lane offsets. The chunk store arrives as
    ``[R, 128]`` lane rows (:func:`_lane_rows`): the kernel copies the
    128-word row that holds a chunk id or a chunk's words into SMEM and
    reads them there as scalars (the lowering refuses scalar reads from
    VMEM, and copies of less than a whole lane row)."""
    c = int(chunk_words)
    per_grp = LANES // c            # chunks per lane group / store row

    def _kernel(seg_start_ref, seg_len_ref, sacc_ref, chunk_id_ref,
                chunk_data_ref, cand_ref, mask_ref, out_ref, ids_buf,
                data_buf, ring_ref, ids_sem, data_sem):
        np_ = seg_start_ref.shape[1]
        sw = sacc_ref.shape[1]
        # dead chunks of the candidate row are pre-zeroed so skipping
        # them below cannot leave stale bits
        out_ref[...] = cand_ref[...] & mask_ref[...]
        lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def chunk_copy(k, slot):
            """Copy the store row holding entry ``k``'s words."""
            return pltpu.make_async_copy(
                chunk_data_ref.at[pl.ds(k // per_grp, 1)],
                data_buf.at[pl.ds(slot, 1)], data_sem.at[slot])

        def row_body(i, _):
            def drain(slot):
                """Wait the copy in ``slot`` and AND its chunk into the
                output row (same-shape descriptor, same semaphore)."""
                chunk_copy(0, slot).wait()
                cid = ring_ref[0, slot]
                src = ring_ref[1, slot]         # chunk's lane in the row
                g = cid // per_grp
                off = (cid % per_grp) * c
                win = out_ref[i, pl.ds(g, 1), :]            # (1, 128)
                for k in range(c):                          # static
                    win = jnp.where(lane == off + k,
                                    win & data_buf[slot, src + k], win)
                out_ref[i, pl.ds(g, 1), :] = win

            def pos_body(p, _):
                k0 = seg_start_ref[i, p]
                nk = seg_len_ref[i, p]                      # 0: inactive

                def walk(j, lc):
                    k = k0 + j

                    # stage the id row holding entry k (on entry and at
                    # every 128-entry boundary of the row's range)
                    @pl.when((j == 0) | (k % LANES == 0))
                    def _():
                        ids = pltpu.make_async_copy(
                            chunk_id_ref.at[pl.ds(k // LANES, 1)],
                            ids_buf, ids_sem)
                        ids.start()
                        ids.wait()

                    cid = ids_buf[0, k % LANES]
                    live = ((sacc_ref[i, cid // 32]
                             >> (cid % 32)) & 1) != 0

                    def issue(lc):
                        slot = lc % depth
                        # free the slot first: its previous chunk is
                        # consumed while this one's copy is in flight
                        @pl.when(lc >= depth)
                        def _():
                            drain(slot)
                        ring_ref[0, slot] = cid
                        ring_ref[1, slot] = (k % per_grp) * c
                        chunk_copy(k, slot).start()
                        return lc + 1

                    return lax.cond(live, issue, lambda lc: lc, lc)

                lc = lax.fori_loop(0, nk, walk, 0)

                def tail(s, _):
                    @pl.when(s < jnp.minimum(lc, depth))
                    def _():
                        drain(s)
                    return 0

                lax.fori_loop(0, depth, tail, 0)
                return 0

            row_live = lax.fori_loop(
                0, sw, lambda s, acc: acc | sacc_ref[i, s], jnp.int32(0))

            @pl.when(row_live != 0)
            def _():
                lax.fori_loop(0, np_, pos_body, 0)
            return 0

        lax.fori_loop(0, rows, row_body, 0)

    return _kernel


@functools.partial(jax.jit,
                   static_argnames=("interpret", "chunk_words", "depth"))
def _refine_rows_hier_call(chunk_id, chunk_data, cand, mask, seg_start,
                           seg_len, sacc, interpret: bool,
                           chunk_words: int, depth: int):
    f_pad, g, _ = cand.shape
    np_ = seg_start.shape[1]
    sw = sacc.shape[1]
    rows = HIER_ROWS
    row_block = pl.BlockSpec((rows, g, LANES), lambda i: (i, 0, 0))

    def smem_rows(width):
        return pl.BlockSpec((rows, width), lambda i: (i, 0),
                            memory_space=pltpu.SMEM)

    return pl.pallas_call(
        _make_refine_hier_kernel(chunk_words, depth, rows),
        grid=(f_pad // rows,),
        in_specs=[
            smem_rows(np_),                             # seg_start
            smem_rows(np_),                             # seg_len
            smem_rows(sw),                              # sacc
            pl.BlockSpec(memory_space=pl.ANY),          # chunk_id  (HBM)
            pl.BlockSpec(memory_space=pl.ANY),          # chunk_data (HBM)
            row_block,                                  # cand
            row_block,                                  # mask
        ],
        out_specs=row_block,
        out_shape=jax.ShapeDtypeStruct((f_pad, g, LANES), jnp.int32),
        scratch_shapes=[
            pltpu.SMEM((1, LANES), jnp.int32),        # staged id row
            pltpu.SMEM((depth, LANES), jnp.int32),    # in-flight rows
            pltpu.SMEM((2, depth), jnp.int32),        # (id, lane) ring
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((depth,)),
        ],
        interpret=interpret,
    )(seg_start, seg_len, sacc, chunk_id, chunk_data, cand, mask)


def refine_bitmap_rows_hier(summary: jax.Array, chunk_ptr: jax.Array,
                            chunk_id: jax.Array, chunk_data: jax.Array,
                            cand_rows: jax.Array, frontier: jax.Array,
                            active: jax.Array,
                            interpret: bool | None = None,
                            dma_depth: int | None = None) -> jax.Array:
    """HBM-paged Eq. 2 refinement over the two-level layout.

    Args:
      summary:    uint32/int32 [V, SW] per-row chunk summary bitmaps.
      chunk_ptr:  int32 [V+1] CSR offsets into the chunk store.
      chunk_id:   int32 [P] stored chunk index per entry.
      chunk_data: uint32/int32 [P, C] the stored chunks.
      cand_rows / frontier / active: as :func:`refine_bitmap_rows`.
      dma_depth:  in-flight chunk copies. None resolves through the
                  tuning layer (kernels.config, DESIGN.md §9).

    The adjacency operands ride in ``pl.ANY`` — nothing O(V·W) is
    staged into VMEM, so the only V-dependent device residency is the
    O(E)-proportional chunk store itself. Returns int32 [F, W_pad]
    (caller slices the first W words).
    """
    if interpret is None:
        interpret = interpret_mode(None)
    v = chunk_ptr.shape[0] - 1
    if dma_depth is None:
        dma_depth = kernel_dma_depth(n_vertices=v)
    dma_depth = max(1, int(dma_depth))
    c = chunk_data.shape[1]
    f, np_ = frontier.shape
    w = cand_rows.shape[1]
    w_pad = max(LANES, _round_up(w, LANES))
    f_pad = _round_up(max(f, 1), HIER_ROWS)
    sacc, mask = summary_intersect(summary, cand_rows, frontier, active,
                                   c, w_pad)
    fr = frontier.astype(jnp.int32).clip(0)
    act = (active != 0) & (frontier >= 0)
    seg_start = chunk_ptr[fr]
    seg_len = jnp.where(act, chunk_ptr[fr + 1] - seg_start, 0)

    def rows(x):
        return jnp.zeros((f_pad,) + x.shape[1:], jnp.int32) \
            .at[:f].set(x.astype(jnp.int32))

    def lane_group_rows(x):
        return rows(x).reshape(f_pad, w_pad // LANES, LANES)

    cand = jnp.zeros((f, w_pad), jnp.int32).at[:, :w].set(
        cand_rows.astype(jnp.int32))
    out = _refine_rows_hier_call(
        _lane_rows(chunk_id), _lane_rows(chunk_data),
        lane_group_rows(cand), lane_group_rows(mask), rows(seg_start),
        rows(seg_len), rows(sacc), bool(interpret), c, dma_depth)
    return out.reshape(f_pad, w_pad)[:f]
