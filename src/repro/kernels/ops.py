"""Jit'd public wrappers around the Pallas kernels.

Every op takes ``backend`` in {"pallas", "pallas_interpret", "jnp"}:
  * ``pallas``           — compiled TPU kernel (target hardware),
  * ``pallas_interpret`` — kernel body interpreted on CPU (what tests and
                           this container use to validate the kernels),
  * ``jnp``              — the pure-jnp oracle from ``ref.py`` (fastest on
                           CPU; also the lowering used by the dry-run).

``backend=None`` resolves from the single process-wide configuration in
``kernels/config.py`` (``set_backend`` / ``REPRO_KERNEL_BACKEND``) — the
same config the engine's device programs consult, so one switch moves
the whole hot path between lowerings and a TPU run cannot silently fall
into interpret mode. ``DEFAULT_BACKEND`` is kept as a module attribute
for backward compatibility and reflects the config default.
"""
from __future__ import annotations

import jax.numpy as jnp

from . import ref
from .bitmap_refine import refine_bitmap as _refine_pallas
from .bitmap_refine import refine_bitmap_rows as _refine_rows_pallas
from .bitmap_refine import \
    refine_bitmap_rows_hier as _refine_rows_hier_pallas
from .bitmap_spmm import bitmap_spmm as _spmm_pallas
from .config import (backend_scope, get_backend, interpret_mode, resolve,
                     set_backend)
from .flash_attention import flash_attention as _flash_pallas

__all__ = ["refine_bitmap_op", "refine_bitmap_rows_op",
           "refine_bitmap_rows_hier_op", "bitmap_spmm_op",
           "flash_attention_op", "get_backend", "set_backend",
           "backend_scope", "DEFAULT_BACKEND"]


def __getattr__(name):
    # DEFAULT_BACKEND tracks the live config (a frozen import-time
    # snapshot would override set_backend() when passed explicitly).
    if name == "DEFAULT_BACKEND":
        return get_backend()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def refine_bitmap_rows_op(adj_bitmap, cand_rows, frontier, active,
                          backend: str | None = None,
                          block_f: int | None = None):
    """Eq. 2 packed-bitmap refinement with per-row candidate/active sets
    (the multi-query wave layout). Returns uint32 [F, W]. ``block_f``
    None resolves through the tuning layer (kernels.config)."""
    w = adj_bitmap.shape[1]
    if resolve(backend) == "jnp":
        return ref.refine_bitmap_rows_ref(adj_bitmap, cand_rows, frontier,
                                          active)
    out = _refine_rows_pallas(adj_bitmap, cand_rows, frontier, active,
                              interpret=interpret_mode(backend),
                              block_f=block_f)
    return out[:, :w].astype(jnp.uint32)


def refine_bitmap_rows_hier_op(summary, chunk_ptr, chunk_id, chunk_data,
                               kmax, cand_rows, frontier, active,
                               backend: str | None = None,
                               dma_depth: int | None = None):
    """Eq. 2 refinement over the two-level (hierarchical) adjacency
    layout — the HBM-resident variant for graphs past the dense
    kernel's VMEM ceiling (kernels.config.use_hbm_adjacency picks the
    variant; core.graph.HierBitmap builds the operands). Bit-identical
    to :func:`refine_bitmap_rows_op` on the same graph. Returns uint32
    [F, W]."""
    w = cand_rows.shape[1]
    if resolve(backend) == "jnp":
        return ref.refine_bitmap_rows_hier_ref(
            summary, chunk_ptr, chunk_id, chunk_data, int(kmax),
            cand_rows, frontier, active)
    out = _refine_rows_hier_pallas(summary, chunk_ptr, chunk_id,
                                   chunk_data, cand_rows, frontier, active,
                                   interpret=interpret_mode(backend),
                                   dma_depth=dma_depth)
    return out[:, :w].astype(jnp.uint32)


def refine_bitmap_op(adj_bitmap, cand_row, frontier, active,
                     backend: str | None = None,
                     block_f: int | None = None):
    """Eq. 2 packed-bitmap refinement, one shared candidate row (the
    single-query layout). Returns uint32 [F, W]."""
    if resolve(backend) == "jnp":
        return ref.refine_bitmap_ref(adj_bitmap, cand_row, frontier, active)
    w = adj_bitmap.shape[1]
    out = _refine_pallas(adj_bitmap, cand_row, frontier, active,
                         interpret=interpret_mode(backend),
                         block_f=block_f)
    return out[:, :w].astype(jnp.uint32)


def bitmap_spmm_op(adj_words, x, backend: str | None = None,
                   block_i: int = 256, block_j: int = 256):
    """Packed-bitmap SpMM ``A @ x``. Returns [N, D] in x.dtype."""
    if resolve(backend) == "jnp":
        return ref.bitmap_spmm_ref(adj_words, x)
    return _spmm_pallas(adj_words, x, block_i=block_i, block_j=block_j,
                        interpret=interpret_mode(backend))


def flash_attention_op(q, k, v, causal: bool = True,
                       backend: str | None = None,
                       block_q: int = 128, block_k: int = 128):
    """Fused attention forward [B, H, S, D] (GQA-aware)."""
    if resolve(backend) == "jnp":
        # oracle handles equal-head layout; expand kv heads for GQA
        h, h_kv = q.shape[1], k.shape[1]
        if h != h_kv:
            rep = h // h_kv
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _flash_pallas(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k,
                         interpret=interpret_mode(backend))
