"""Single kernel-backend configuration shared by every kernel call site.

Before this module existed, ``bitmap_refine.refine_bitmap`` defaulted to
``interpret=True`` while ``ops.py`` owned its own ``DEFAULT_BACKEND`` —
a TPU run that called the kernel directly (or through ``engine_step``)
could silently fall into interpret mode. Now *one* process-wide setting
decides how every op lowers:

  * ``"jnp"``              — pure-jnp oracle path (``ref.py``); fastest on
                             CPU and what the dry-run lowers by default.
  * ``"pallas_interpret"`` — Pallas kernel bodies interpreted on CPU (the
                             kernel-validation mode used by the tests;
                             refused where JAX's backend is not the CPU).
  * ``"pallas"``           — compiled TPU kernels (target hardware).

Resolution order: explicit ``backend=`` argument > ``set_backend()`` >
``REPRO_KERNEL_BACKEND`` environment variable > ``"jnp"``.

Kernel wrappers translate the backend to their ``interpret`` flag with
:func:`interpret_mode` — so ``interpret=True`` can only happen when the
configuration explicitly asks for it.

Since the autotuner (DESIGN.md §9) this module is also the resolution
point for tuned *kernel* parameters: :func:`kernel_block_f` resolves the
``bitmap_refine`` row-block height as explicit scope override >
tuning-cache record (for the call's backend and graph size) > built-in
``DEFAULT_BLOCK_F``. :func:`backend_scope` / :func:`kernel_param_scope`
give tests and the tuner leak-free save/restore around the
process-global state.
"""
from __future__ import annotations

import contextlib
import os

BACKENDS = ("jnp", "pallas_interpret", "pallas")

DEFAULT_BLOCK_F = 8     # refine kernel sublanes per grid step
                        # (int32 min tile height; see bitmap_refine.py)

DEFAULT_CHUNK_WORDS = 8  # hierarchical layout: packed words per chunk
                         # (C) — 256 vertices of coverage per summary bit
DEFAULT_DMA_DEPTH = 2    # in-flight chunk copies in the HBM refine
                         # kernel's double-buffered pipeline

# Dense/hierarchical threshold: below this many data-graph vertices the
# dense kernel holds the whole padded adjacency as one single-buffered
# VMEM block (16,383 vertices is 32 MiB of the v5e's 128 MiB VMEM; the
# TPU compiler takes 28,672 vertices, 98 MiB, and refuses 30,720);
# at or above it the adjacency stays in HBM and the hierarchical kernel
# copies only live chunks (DESIGN.md §2). tests/test_tpu_compile.py
# compiles the dense kernel at the largest size this sends to it. A
# tuning record or kernel_param_scope override ("hbm_adjacency") wins
# over the threshold.
HBM_ADJACENCY_MIN_VERTICES = 16384

# scope-local kernel parameter overrides (kernel_param_scope) — the
# "explicit arg" level of the tuning resolution order
_kernel_overrides: dict[str, int] = {}

_backend = os.environ.get("REPRO_KERNEL_BACKEND", "jnp")
if _backend not in BACKENDS:
    raise ValueError(
        f"REPRO_KERNEL_BACKEND={_backend!r} not in {BACKENDS}")


def get_backend() -> str:
    """The process-wide kernel backend."""
    return _backend


def set_backend(name: str) -> None:
    """Set the process-wide kernel backend (e.g. once at TPU startup)."""
    global _backend
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"choose one of {BACKENDS}")
    _backend = name


@contextlib.contextmanager
def backend_scope(name: str):
    """Temporarily switch the process-wide backend — save/restore around
    :func:`set_backend`, exception-safe, so tests and the tuner can
    sweep backends without leaking process-global state."""
    prev = get_backend()
    set_backend(name)
    try:
        yield name
    finally:
        set_backend(prev)


@contextlib.contextmanager
def kernel_param_scope(**params: int):
    """Temporarily pin tuned kernel parameters (e.g. ``block_f=16``) —
    the explicit-override level of the resolution order, used by the
    tuner to measure candidate points and by tests to pin geometry."""
    global _kernel_overrides
    prev = dict(_kernel_overrides)
    _kernel_overrides.update({k: int(v) for k, v in params.items()})
    try:
        yield dict(_kernel_overrides)
    finally:
        _kernel_overrides = prev


def kernel_override(name: str) -> int | None:
    """The active :func:`kernel_param_scope` override for ``name``."""
    return _kernel_overrides.get(name)


def _tuned_param(name: str, backend: str | None,
                 n_vertices: int | None) -> int | None:
    """Shared knob lookup: scope override > tuning-cache record for
    (backend, device kind, |V| bucket) > None (caller's built-in)."""
    v = _kernel_overrides.get(name)
    if v is not None:
        return int(v)
    if n_vertices is not None \
            and os.environ.get("REPRO_TUNING_DISABLE") != "1":
        from ..tuning.cache import device_kind, load_default_cache
        rec = load_default_cache().lookup(
            resolve(backend), device_kind(), n_vertices)
        if rec and name in rec.get("params", {}):
            return int(rec["params"][name])
    return None


def kernel_block_f(backend: str | None = None,
                   n_vertices: int | None = None) -> int:
    """Resolved ``bitmap_refine`` row-block height: scope override >
    tuning-cache record (needs ``n_vertices`` for the shape bucket) >
    ``DEFAULT_BLOCK_F``. Called at trace time by the kernel wrapper
    when no explicit ``block_f`` argument was passed."""
    v = _tuned_param("block_f", backend, n_vertices)
    return DEFAULT_BLOCK_F if v is None else v


def kernel_chunk_words(backend: str | None = None,
                       n_vertices: int | None = None) -> int:
    """Resolved hierarchical chunk width C (words per chunk), same
    resolution order as :func:`kernel_block_f`."""
    v = _tuned_param("chunk_words", backend, n_vertices)
    return DEFAULT_CHUNK_WORDS if v is None else v


def kernel_dma_depth(backend: str | None = None,
                     n_vertices: int | None = None) -> int:
    """Resolved DMA pipeline depth of the HBM-resident refine kernel
    (in-flight chunk copies), same resolution order as
    :func:`kernel_block_f`."""
    v = _tuned_param("dma_depth", backend, n_vertices)
    return DEFAULT_DMA_DEPTH if v is None else max(1, v)


def use_hbm_adjacency(backend: str | None = None,
                      n_vertices: int | None = None) -> bool:
    """Whether refinement should use the hierarchical / HBM-resident
    layout at this graph size: scope override ("hbm_adjacency", 0/1) >
    tuning-cache record > the ``HBM_ADJACENCY_MIN_VERTICES``
    threshold."""
    v = _tuned_param("hbm_adjacency", backend, n_vertices)
    if v is not None:
        return bool(v)
    return (n_vertices is not None
            and int(n_vertices) >= HBM_ADJACENCY_MIN_VERTICES)


def resolve(backend: str | None) -> str:
    """An explicit per-call backend wins; None means the global config.

    ``"pallas_interpret"`` resolves only where JAX's default backend is
    the CPU: on an accelerator it would run every kernel in the host
    interpreter and report that as the device's speed."""
    name = get_backend() if backend is None else backend
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"choose one of {BACKENDS}")
    if name == "pallas_interpret":
        import jax
        platform = jax.default_backend()
        if platform != "cpu":
            raise ValueError(
                f"kernel backend 'pallas_interpret' on a {platform!r} "
                "device; use 'pallas' to compile the kernels for it")
    return name


def interpret_mode(backend: str | None) -> bool:
    """Interpret flag for a Pallas call under ``backend`` (None = global).

    Only ``"pallas_interpret"`` interprets; ``"pallas"`` compiles for the
    accelerator. (``"jnp"`` never reaches a pallas_call.)
    """
    return resolve(backend) == "pallas_interpret"
