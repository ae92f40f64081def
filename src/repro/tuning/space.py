"""Declarative search space for the kernel & schedule autotuner
(DESIGN.md §9).

A :class:`TunableSpace` enumerates candidate configurations over the
engine's hot-path knobs — the ``bitmap_refine`` row-block height
(``block_f``), megastep fusion depth, device stack capacity, pattern
store capacity (with its PROBE-window floor), and the scheduler packing
knobs (``wave_size``, ``n_slots``, ``store_flush_min``) — and rejects
invalid points *before* anything compiles:

* pow-2 constraints (``wave_size``, ``stack_capacity``,
  ``pattern_capacity``) — the store's open-addressing mask and the
  stack ring arithmetic require them;
* ``pattern_capacity >= PROBE`` (the linear probe window must fit);
* ``block_f`` must be a multiple of 8 on the compiled ``pallas``
  backend (int32 min tile is (8, 128) sublanes x lanes); interpret /
  jnp runs accept any height >= 1 (the oracle-equality tests exploit
  this with a deliberately odd block height);
* a VMEM budget at the given ``(V, W)`` shape: the dense refine kernel
  (``hbm_adjacency=0``) holds the whole padded adjacency bitmap plus
  its candidate/output row blocks in VMEM, so points whose working set
  exceeds the budget are rejected with a reason instead of failing at
  compile time. The hierarchical variant (``hbm_adjacency=1``) leaves
  the adjacency in HBM and only budgets its row blocks, so large-V
  points stay admissible there and the dense rejection explains *why*
  the layout switches;
* hierarchical layout knobs: ``chunk_words`` must be a power of two in
  [1, 128] (the summary packs one bit per chunk into u32 words and the
  kernel slices chunk-aligned word windows), ``dma_depth >= 1``.

The schema hash over this definition is the staleness key for
TUNING_CACHE.json: a record written under a different knob schema is
ignored (see ``tuning/cache.py``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json

__all__ = ["CandidateConfig", "TunableSpace", "WorkloadShape",
           "schema_hash", "DEFAULT_VMEM_BUDGET_BYTES"]

# The store's linear probe window (patterns/store.py PROBE): capacity
# below it cannot hold one probe sequence. Kept as a literal here so the
# space is importable without the patterns package; pinned equal by
# tests/test_tuning.py.
PROBE = 8

# VMEM budget for the refine kernel's resident working set. A v5e core
# has 128 MiB of VMEM; compiled for it, the dense kernel took a 98 MiB
# adjacency block (28,672 vertices) and was refused at 113 MiB (30,720
# vertices), so 96 MiB leaves the compiler its own buffers.
DEFAULT_VMEM_BUDGET_BYTES = 96 * 1024 * 1024

# wave rows per grid step of the hier kernel (bitmap_refine.HIER_ROWS)
HIER_ROWS = 8

# The knob schema the cache's staleness hash covers: names, domains and
# the constraint version. Bump ``constraints`` whenever a validity rule
# changes meaning — every cached record becomes stale at once.
_SCHEMA = {
    "version": 1,
    "constraints": 2,
    "knobs": {
        "block_f": [4, 8, 16, 32],
        "megastep_depth": [1, 2, 4, 6, 8, 12],
        "wave_size": [32, 64, 128, 256, 512, 1024],
        "n_slots": [1, 2, 4, 8, 16, 32, 64],
        "stack_capacity": [256, 512, 1024, 2048, 4096],
        "pattern_capacity": [64, 128, 256, 512, 1024, 2048, 4096],
        "store_flush_min": [1, 8, 16, 32, 64],
        # hierarchical / HBM-resident adjacency (DESIGN.md §2)
        "hbm_adjacency": [0, 1],
        "chunk_words": [1, 2, 4, 8, 16, 32],
        "dma_depth": [1, 2, 4],
    },
}

KNOB_NAMES = tuple(sorted(_SCHEMA["knobs"]))


def schema_hash() -> str:
    """Digest of the knob schema — the cache staleness key."""
    blob = json.dumps(_SCHEMA, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:12]


def _is_pow2(v: int) -> bool:
    return v >= 1 and (v & (v - 1)) == 0


@dataclasses.dataclass(frozen=True)
class WorkloadShape:
    """The quantities the validity constraints need: data-graph vertex
    count (``v``), packed bitmap word width (``w``), and the widest
    query the engine pads to (``n_pad``)."""
    v: int
    w: int
    n_pad: int = 64

    @staticmethod
    def for_graph(n_vertices: int, n_pad: int = 64) -> "WorkloadShape":
        return WorkloadShape(v=int(n_vertices),
                             w=(int(n_vertices) + 31) // 32,
                             n_pad=n_pad)


@dataclasses.dataclass(frozen=True)
class CandidateConfig:
    """One point of the search space. ``as_params()`` is the dict shape
    the cache records and the resolution layer consume."""
    block_f: int = 8
    megastep_depth: int = 6
    wave_size: int = 512
    n_slots: int = 8
    stack_capacity: int = 1024
    pattern_capacity: int = 1024
    store_flush_min: int = 16
    hbm_adjacency: int = 0
    chunk_words: int = 8
    dma_depth: int = 2

    def as_params(self) -> dict:
        return {k: int(getattr(self, k)) for k in KNOB_NAMES}


def refine_vmem_bytes(shape: WorkloadShape, block_f: int) -> int:
    """Resident VMEM bytes of the dense refine kernel at ``shape``: the
    whole padded adjacency block (single-buffered) plus the candidate
    and output row blocks (two pipeline buffers each, int32 words),
    mirroring ``bitmap_refine``'s padding rules."""
    w_pad = max(128, ((shape.w + 127) // 128) * 128)
    v_pad = ((shape.v + 7) // 8) * 8
    adj = v_pad * w_pad * 4
    row_blocks = 2 * 2 * block_f * w_pad * 4    # cand + out, 2 buffers
    return adj + row_blocks


def refine_hier_vmem_bytes(shape: WorkloadShape) -> int:
    """Resident VMEM bytes of the *hierarchical* refine kernel: the
    adjacency stays in HBM and its chunk ids and words pass through
    SMEM, so VMEM holds only the candidate, mask and output row blocks
    (``HIER_ROWS`` rows of w_pad words, two pipeline buffers each)."""
    w_pad = max(128, ((shape.w + 127) // 128) * 128)
    return 3 * 2 * HIER_ROWS * w_pad * 4


class TunableSpace:
    """Candidate enumeration + validity checking for one backend at one
    workload shape."""

    def __init__(self, backend: str, shape: WorkloadShape,
                 vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES):
        self.backend = backend
        self.shape = shape
        self.vmem_budget_bytes = int(vmem_budget_bytes)
        self.rejected: list[tuple[CandidateConfig, str]] = []

    # -- validity ------------------------------------------------------
    def validate(self, cfg: CandidateConfig) -> str | None:
        """``None`` when ``cfg`` is admissible, else the rejection
        reason. Pure shape arithmetic — nothing here compiles."""
        for name in ("block_f", "megastep_depth", "wave_size", "n_slots",
                     "stack_capacity", "pattern_capacity",
                     "store_flush_min", "chunk_words", "dma_depth"):
            if getattr(cfg, name) < 1:
                return f"{name} must be >= 1"
        if cfg.hbm_adjacency not in (0, 1):
            return f"hbm_adjacency={cfg.hbm_adjacency} must be 0 or 1"
        if cfg.chunk_words > 128 or not _is_pow2(cfg.chunk_words):
            return (f"chunk_words={cfg.chunk_words} must be a power of "
                    "two in [1, 128] (summary packs one bit per chunk "
                    "into u32 words)")
        for name in ("wave_size", "stack_capacity", "pattern_capacity"):
            if not _is_pow2(getattr(cfg, name)):
                return f"{name}={getattr(cfg, name)} is not a power of two"
        if cfg.pattern_capacity < PROBE:
            return (f"pattern_capacity={cfg.pattern_capacity} below the "
                    f"probe window ({PROBE})")
        if self.backend == "pallas" and cfg.block_f % 8:
            return (f"block_f={cfg.block_f} not a multiple of the int32 "
                    "sublane tile (8) on the compiled pallas backend")
        if cfg.stack_capacity < cfg.wave_size:
            return (f"stack_capacity={cfg.stack_capacity} below "
                    f"wave_size={cfg.wave_size} (a full wave of fresh "
                    "roots must fit one stack bank)")
        if cfg.hbm_adjacency:
            need = refine_hier_vmem_bytes(self.shape)
            if need > self.vmem_budget_bytes:
                return (f"hier refine row blocks {need} B exceed the VMEM "
                        f"budget {self.vmem_budget_bytes} B at "
                        f"V={self.shape.v}")
            return None
        need = refine_vmem_bytes(self.shape, cfg.block_f)
        if need > self.vmem_budget_bytes:
            return (f"refine working set {need} B exceeds the VMEM "
                    f"budget {self.vmem_budget_bytes} B at "
                    f"V={self.shape.v}")
        return None

    # -- enumeration ---------------------------------------------------
    def candidates(self, overrides: dict[str, list] | None = None
                   ) -> list[CandidateConfig]:
        """Valid candidates from the cross product of the knob domains
        (``overrides`` narrows any knob's domain — the smoke tuner uses
        this to keep CI runs to a handful of points). Invalid points
        land in ``self.rejected`` with their reason."""
        domains = {k: list(v) for k, v in _SCHEMA["knobs"].items()}
        for k, vals in (overrides or {}).items():
            if k not in domains:
                raise KeyError(f"unknown tunable knob {k!r}; "
                               f"known: {sorted(domains)}")
            domains[k] = list(vals)
        out = []
        names = KNOB_NAMES
        for values in itertools.product(*(domains[n] for n in names)):
            cfg = CandidateConfig(**dict(zip(names, values)))
            reason = self.validate(cfg)
            if reason is None:
                out.append(cfg)
            else:
                self.rejected.append((cfg, reason))
        return out
