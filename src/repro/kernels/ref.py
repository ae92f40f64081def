"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FULL_U32 = np.uint32(0xFFFFFFFF)     # host constant: no backend at import


def refine_bitmap_ref(adj_bitmap: jax.Array, cand_row: jax.Array,
                      frontier: jax.Array, active: jax.Array) -> jax.Array:
    """Eq. 2 refinement oracle: cand ∧ ⋀_{p active} adj[frontier[:, p]].

    Same signature/semantics as kernels.bitmap_refine.refine_bitmap but
    returns uint32 [F, W] (unpadded).
    """
    f, np_ = frontier.shape
    adj = adj_bitmap.astype(jnp.uint32)
    acc = jnp.broadcast_to(cand_row.astype(jnp.uint32)[None, :],
                           (f, adj.shape[1]))

    def body(p, acc):
        act = (active[p] != 0)
        rows = adj[frontier[:, p].clip(0)]
        rows = jnp.where((frontier[:, p] >= 0)[:, None], rows, FULL_U32)
        return jnp.where(act, acc & rows, acc)

    return jax.lax.fori_loop(0, np_, body, acc)


def refine_bitmap_rows_ref(adj_bitmap: jax.Array, cand_rows: jax.Array,
                           frontier: jax.Array, active: jax.Array
                           ) -> jax.Array:
    """Per-row Eq. 2 oracle (multi-query layout): candidates and active
    positions vary per row. Same semantics as
    ``kernels.bitmap_refine.refine_bitmap_rows``; returns uint32 [F, W].
    """
    f, np_ = frontier.shape
    adj = adj_bitmap.astype(jnp.uint32)
    acc = cand_rows.astype(jnp.uint32)

    def body(p, acc):
        act = (active[:, p] != 0) & (frontier[:, p] >= 0)
        rows = adj[frontier[:, p].clip(0)]
        return jnp.where(act[:, None], acc & rows, acc)

    return jax.lax.fori_loop(0, np_, body, acc)


def refine_bitmap_rows_hier_ref(summary: jax.Array, chunk_ptr: jax.Array,
                                chunk_id: jax.Array,
                                chunk_data: jax.Array, kmax: int,
                                cand_rows: jax.Array, frontier: jax.Array,
                                active: jax.Array) -> jax.Array:
    """Eq. 2 oracle over the two-level (hierarchical) adjacency layout
    (core.graph.HierBitmap) — bit-identical to
    :func:`refine_bitmap_rows_ref` on the dense bitmap of the same
    graph.

    Exercises both levels the way the HBM kernel does: the summary
    intersection ``sacc = cand_summary ∧ ⋀_p summary[frontier_p]``
    pre-zeroes dead chunks (sound: a dead chunk is zero in the dense
    result — either the candidate chunk was empty or some active row
    misses it entirely), then each active position's row is
    reconstructed from its stored chunks and AND-folded. ``kmax`` is
    the layout's static max stored-chunks-per-row.

    Returns uint32 [F, W] where W = cand_rows.shape[1].
    """
    f, np_ = frontier.shape
    w = cand_rows.shape[1]
    c = chunk_data.shape[1]
    sw = summary.shape[1]
    ncp = sw * 32                       # padded chunk count (>= ceil(W/C))
    acc = cand_rows.astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)

    cpad = jnp.zeros((f, ncp * c), jnp.uint32).at[:, :w].set(acc)
    nonzero = (cpad.reshape(f, ncp, c) != 0).any(axis=2)
    cand_sum = (nonzero.reshape(f, sw, 32).astype(jnp.uint32)
                << shifts).sum(axis=2, dtype=jnp.uint32).astype(jnp.uint32)

    def sbody(p, s):
        act = (active[:, p] != 0) & (frontier[:, p] >= 0)
        rows = summary.astype(jnp.uint32)[frontier[:, p].clip(0)]
        return jnp.where(act[:, None], s & rows, s)

    sacc = jax.lax.fori_loop(0, np_, sbody, cand_sum)
    livebit = ((sacc[:, :, None] >> shifts) & jnp.uint32(1))
    mask = jnp.repeat(livebit.reshape(f, ncp), c,
                      axis=1)[:, :w] * FULL_U32
    acc = acc & mask

    def body(p, acc):
        vtx = frontier[:, p]
        act = (active[:, p] != 0) & (vtx >= 0)
        k0 = chunk_ptr[vtx.clip(0)]
        nk = chunk_ptr[vtx.clip(0) + 1] - k0
        ks = k0[:, None] + jnp.arange(kmax)[None, :]
        km = jnp.arange(kmax)[None, :] < nk[:, None]
        ids = jnp.where(km, chunk_id[ks], ncp)          # pad -> dropped
        data = jnp.where(km[:, :, None],
                         chunk_data[ks].astype(jnp.uint32), jnp.uint32(0))
        rows = jnp.zeros((f, ncp, c), jnp.uint32).at[
            jnp.arange(f)[:, None], ids].set(data, mode="drop")
        rows = rows.reshape(f, ncp * c)[:, :w]
        return jnp.where(act[:, None], acc & rows, acc)

    return jax.lax.fori_loop(0, np_, body, acc)


def bitmap_spmm_ref(adj_words: jax.Array, x: jax.Array) -> jax.Array:
    """Unpack the bitmap densely and matmul in f32."""
    n, w = adj_words.shape
    words = adj_words.astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    dense = bits.reshape(n, w * 32).astype(jnp.float32)
    return (dense @ x.astype(jnp.float32)).astype(x.dtype)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """Plain softmax attention oracle, [B, H, S, D] layout, f32 math."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    logits = jnp.einsum("bhsd,bhtd->bhst", qf, kf) * scale
    if causal:
        s, t = logits.shape[-2:]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs, vf).astype(q.dtype)
