"""Host-side shared-wave scheduler for the TPU matching engine.

Continuous multi-query wave batching (DESIGN.md §2): many concurrent
queries are admitted into bank *slots*; every wave is packed with ready
segment rows from whichever queries have work, so one fixed-shape jitted
device program serves mixed traffic with no idle gaps between queries.
The per-query DFS stacks and Lemma-4 resolution bookkeeping live in
``segments.py``; all dense work — Eq. 2 refinement, injectivity,
dead-end lookup, child extraction, pattern scatter — runs in the jitted
device programs of ``engine_step``.

Megastep & async pipeline (DESIGN.md §2): with ``megastep_depth > 1``
each packed wave is dispatched as one fused ``run_megastep_mq`` program
that executes up to K consecutive depth-steps on a device-resident ring
buffer — child assembly, dead-end lookups, embedding emission, and the
batched pattern flush all happen in-loop, and only a compact digest
returns to the host. ``step()`` is double-buffered: megastep *i+1* is
dispatched (JAX async dispatch, nothing materialized) *before* megastep
*i*'s digest is read, so host bookkeeping overlaps device compute
instead of serializing on ~14 per-wave ``np.asarray`` syncs as the
single-step path did. ``megastep_depth == 1`` keeps the synchronous
single-step path (`expand_wave_mq` + host assembly) as the oracle
reference schedule.

Scheduling policy: admission fills free slots from a bounded FIFO queue;
wave packing round-robins over active queries, splitting segment slices
so waves stay full. The one-item-per-query rule is the fair-share
*floor*: on the fused megastep schedule a query may contribute up to
``max(1, wave_size / n_active)`` items per wave (occupancy-aware
packing — a lone heavy query fills the wave), while the single-step
schedule keeps the strict one-item store→lookup cadence. A query
submitted with ``parallelism = k`` runs as k intra-query shards
(shard-as-segments, DESIGN.md §3): k root segments with per-shard DFS
stacks, work stealing on work-item ranges, and one shared slot-private
table so every pattern (μ > 0 included) crosses shards for free.
Per-query ``limit`` / ``max_rows`` / ``time_budget_s`` abort a query
and evict its segments without touching its neighbors.

Learning happens *across* waves: patterns extracted from failures in
earlier-expanded subtrees prune later waves of the same query (stores are
slot-private, so live queries never see each other's patterns), and the
megastep additionally stores Lemma-1 patterns *inside* the loop, so they
prune later depth-steps of the same dispatch. Δ itself is the bounded
hashed store of :mod:`repro.patterns.store` — O(configured capacity)
device memory regardless of data-graph size, with counter-guided
eviction — and learning additionally crosses *queries* through the
template cache (:mod:`repro.patterns.cache`, DESIGN.md §6): a retiring
learner snapshots its hot transferable patterns and an admission of an
identical template warm-starts from them. Matching is exact for any
schedule, capacity, or seed because stored patterns are true dead-ends.

The public face of all of this is the request/handle API of
:mod:`repro.api` (DESIGN.md §4): a ``MatchSession`` wraps a scheduler,
``submit()`` is non-blocking and returns a ``MatchHandle`` whose
``stream()`` consumes the per-query embedding deliveries this module
pushes out of ``_retire_mega``/``_process_wave`` (``_deliver``), and
``cancel()`` rides :meth:`WaveScheduler.cancel` onto the existing
eviction path. Every knob resolves through ``repro.api.MatchOptions``
— the single default surface shared with the server and the
distributed matcher. :class:`WaveEngine` is the single-query blocking
facade (one slot) kept for the sequential-style API; the distributed
matcher fronts the same session machinery (shard-as-segments,
``core.distributed``).
"""
from __future__ import annotations

import collections
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..api.options import MatchOptions
from ..kernels.config import (get_backend, kernel_chunk_words,
                              kernel_dma_depth, use_hbm_adjacency)

_log = logging.getLogger(__name__)
from ..patterns import (DeadEndStats, PatternCache, PatternStore,
                        PatternStoreBank, age_hits, empty_entries,
                        entries_to_store, store_to_entries)
from .backtrack import MatchResult, _prepare
from .faults import DISPATCH_ERRORS, FaultInjected, corrupt_digest
from .engine_step import (MASK_WORDS, N_PAD, STK_FREE, STK_FRESH,
                          STK_LEFT, STK_RES, STK_WAIT, DeviceResult,
                          GraphArrays, MegaResult, QueryBank, StackBank,
                          assemble_children_mq, clear_slot_stack,
                          clear_slot_stacks, expand_wave_mq,
                          extract_more_mq, load_slot, load_slots,
                          read_store_slot, run_device_megastep,
                          run_megastep_mq, store_patterns_mq)
from . import spans
from .graph import Graph, pack_bitmap
from .spans import Acc, span
from .segments import (EngineStats, QueryState, Segment, SegmentPool,
                       WorkItem, below, bit_of, mask64, words_from64)

__all__ = ["WaveScheduler", "WaveEngine", "EngineStats", "QueueFull",
           "match_vectorized"]


class QueueFull(RuntimeError):
    """Raised when the bounded admission queue rejects a submission."""


# per-slot scalar lanes of a DeviceResult digest, materialized as one
# dict so the validator / fault injector can address them uniformly
_DEV_LANES = ("d_accepted", "d_expanded", "d_rows", "d_prunes", "d_inj",
              "d_stored", "d_pending", "d_live", "d_outsum",
              "d_childlive")


@dataclasses.dataclass
class _Request:
    """A prepared query waiting in the admission queue."""
    query_id: int
    n: int
    order: np.ndarray
    roots: np.ndarray
    cand_bitmap: np.ndarray        # uint32 [N_PAD, W]
    nbr_mask: np.ndarray           # bool [N_PAD, N_PAD]
    qnbr_bits: np.ndarray          # uint64 [N_PAD]
    limit: int | None
    learn: bool
    max_rows: int | None
    time_budget_s: float | None
    seed_patterns: dict | None     # entries dict (patterns.store)
    keep_table: bool
    t_submit: float
    # canonical template key (patterns.cache); None when the scheduler
    # runs cache-less — the SHA-1 over the packed candidate bitmap is
    # not free at web-scale V, so it is only computed when consumed
    fingerprint: bytes | None
    parallelism: int = 1
    # priority-aware admission: higher admitted first, FIFO within a tie
    priority: int = 0
    # streamed-embedding sink (MatchHandle._push); None = no streaming
    on_embeddings: object | None = None
    # called when the query first takes a slot (MatchHandle._admitted)
    on_admit: object | None = None
    # ---- degraded-mode replay (DESIGN.md §8) --------------------------
    # a quarantined query is re-admitted as a fresh request on the host
    # single-step fallback path, carrying the embeddings it already
    # found (deduplicated on replay) and its failure count
    host_only: bool = False
    fail_count: int = 0
    prior_embeddings: list | None = None   # [n_query] int32 rows
    emb_seen: set | None = None            # tobytes() of every prior row
    prior_rows: int = 0                    # rows_created before demotion
    prior_ttfe: float | None = None


@dataclasses.dataclass
class _Inflight:
    """A dispatched-but-unread device wave (the pipeline's depth-1 slot).

    ``res`` holds unmaterialized device arrays; reading any of them
    blocks until the dispatch finishes, which the scheduler postpones
    until the *next* wave is already on its way.
    """
    kind: str                      # "mega" | "leftover"
    res: object                    # MegaResult | extract_more_mq tuple
    metas: list                    # [(q, seg, s, e, woff, k)]
    slot_map: dict                 # slot -> QueryState at dispatch time
    fr: np.ndarray | None = None   # leftover kind: packed inputs for
    us: np.ndarray | None = None   # host-side child assembly
    ph: np.ndarray | None = None
    depth_v: np.ndarray | None = None
    t_dispatch: float = 0.0        # watchdog reference point
    hung: bool = False             # injected hang: digest untrusted


@dataclasses.dataclass
class _InflightDev:
    """A dispatched-but-unread device-resident dispatch (stack path).

    The digest is per-slot scalars plus the embedding batch — no per-row
    lanes ever cross back; ``slot_map`` snapshots slot ownership at
    dispatch time so a slot recycled mid-flight drops the stale digest.
    """
    res: DeviceResult              # unmaterialized device digest
    slot_map: dict                 # slot -> QueryState at dispatch time
    root_slots: tuple              # slots whose root batch rode along
    t_max: int
    t_dispatch: float = 0.0        # watchdog reference point
    hung: bool = False             # injected hang: digest untrusted


class WaveScheduler:
    """Continuous multi-query matching over one data graph.

    Usage::

        sched = WaveScheduler(data_graph, n_slots=16)
        qid = sched.submit(query_graph, limit=1000)
        sched.run()
        res = sched.finished.pop(qid)          # MatchResult

    ``megastep_depth`` — K consecutive depth-steps fused into one device
    dispatch (1 = the synchronous single-step reference schedule).
    ``store_flush_min`` — single-step path only: host-queued pattern
    stores are batched across waves until this many are pending (the
    megastep path fuses the flush into every dispatch instead).

    Every knob lives on :class:`repro.api.MatchOptions` — pass a
    resolved ``options`` object or the equivalent keyword overrides;
    defaults come from ``MatchOptions`` alone (no local copies), and
    the instance's ``options`` doubles as the default per-query options
    for :meth:`submit`.
    """

    def __init__(self, data: Graph, *,
                 options: MatchOptions | None = None, **knobs):
        opts = MatchOptions.resolve(options, **knobs)
        self.options = opts
        self.data = data
        self._kernel_backend = get_backend()
        # tuning resolution (DESIGN.md §9): every tunable knob the
        # caller left None fills from the persistent tuning cache
        # (keyed by backend / device kind / quantized |V|), else the
        # built-in default. Explicit values — options or kwargs — win.
        tuned, self.tuning_record = opts.resolved_engine(
            backend=self._kernel_backend, n_vertices=data.n)
        _log.info(
            "WaveScheduler tuning: %s (%s) for backend=%s |V|=%d -> %s",
            self.tuning_record["source"],
            self.tuning_record["record"] or "built-in defaults",
            self._kernel_backend, data.n, tuned)
        self.n_slots = tuned["n_slots"]
        self.wave_size = tuned["wave_size"]
        self.kpr = int(opts.kpr)
        self.use_pruning = (True if opts.use_pruning is None
                            else opts.use_pruning)
        self.max_queue = int(opts.max_queue)
        self.megastep_depth = tuned["megastep_depth"]
        self.store_flush_min = tuned["store_flush_min"]
        self.store_pad = int(opts.store_pad)
        self._block_f = tuned["block_f"]
        # bounded hashed Δ store (patterns.store): per-slot capacity is a
        # power of two, independent of the data-graph vertex count.
        # Eviction is counter-guided and always sound; ``hit_decay_every``
        # waves the device hit counters are halved so eviction tracks
        # recent usefulness.
        self.pattern_capacity = tuned["pattern_capacity"]
        self.hit_decay_every = int(opts.hit_decay_every)
        # cross-query template cache (patterns.cache): retiring learners
        # snapshot their hot transferable (μ == 0) patterns; admissions
        # of an identical template warm-start from them.
        self.pattern_cache = (
            PatternCache(opts.pattern_cache_templates,
                         opts.pattern_cache_top_k)
            if opts.pattern_cache else None)
        # deferred cache snapshots: a retiring learner's slot store is
        # captured as async device slices (no host block on the in-
        # flight pipeline) and folded into the cache only if the same
        # template is admitted again — never-repeated templates pay
        # nothing. Bounded LRU alongside the cache itself.
        self._pending_snaps: collections.OrderedDict[bytes, tuple] = \
            collections.OrderedDict()
        self.warm_started = 0           # queries admitted with a warm Δ
        self.warm_patterns_seeded = 0
        # aggregate device store counters (megastep digests + flushes).
        # Flush counters accumulate as an unmaterialized device sum and
        # fold at ownership-change points (query finish) and stats reads
        # — materializing per flush would serialize the async pipeline.
        self.store_counters = {"stored": 0, "overwrites": 0,
                               "evictions": 0, "dropped": 0}
        self._flush_ctr_dev = None          # lazy StoreCounters sum
        self._last_aged_wave = 0
        # adaptive depth: a per-wave prune-rate EMA decides between the
        # fused K-deep megastep (cheap traffic: latency hiding wins) and
        # the synchronous single-step schedule (failure-heavy traffic:
        # the paper's tight store→lookup cadence wins — K-deep
        # speculation would expand rows that fresh patterns could have
        # pruned). Starts at 1.0 = assume prune-heavy until proven easy.
        self.adaptive_prune_threshold = float(
            opts.adaptive_prune_threshold)
        self._prune_ema = 1.0
        # the megastep extracts with a deeper per-row cap than the
        # single-step path: every child beyond the cap forces a
        # host-round-trip leftover pass, which is exactly what the fused
        # loop exists to avoid (hub vertices overflow kpr=8 routinely).
        self._mega_kpr = 2 * self.kpr
        # ring capacity: one chunk's worst-case fan-out (F·kpr) must fit
        # above the tail at every iteration (the megastep's conservative
        # overflow guard), with 2x slack so typical fan-outs get several
        # depth-steps before the guard trips.
        self._ring_capacity = 2 * self.wave_size * (self._mega_kpr + 1)
        self._emb_cap = 2 * self.wave_size * self._mega_kpr
        self.w = (data.n + 31) // 32
        # adjacency layout (DESIGN.md §2): options pin wins, else the
        # kernels.config size threshold / tuning record decides. The
        # hierarchical path never materializes the dense [V, W] block —
        # at 64K vertices that block alone is 512 MB, the thing the
        # layout exists to avoid.
        self._use_hier = (bool(opts.hier_adjacency)
                          if opts.hier_adjacency is not None
                          else use_hbm_adjacency(self._kernel_backend,
                                                 data.n))
        if self._use_hier:
            cw = (int(opts.chunk_words) if opts.chunk_words is not None
                  else kernel_chunk_words(self._kernel_backend, data.n))
            self._dma_depth = (
                int(opts.dma_depth) if opts.dma_depth is not None
                else kernel_dma_depth(self._kernel_backend, data.n))
            hb = data.hier_bitmap(chunk_words=cw)
            self._chunk_words = cw
            self.g = GraphArrays(
                adj_bitmap=None,
                n_vertices=jnp.int32(data.n),
                adj_summary=jnp.asarray(hb.summary),
                chunk_ptr=jnp.asarray(hb.chunk_ptr),
                chunk_id=jnp.asarray(hb.chunk_id),
                chunk_data=jnp.asarray(hb.chunk_data),
                chunk_pad=jnp.zeros((hb.kmax,), jnp.int32))
            self.adjacency_variant = "hier-hbm"
            self.adjacency_bytes = int(hb.nbytes)
        else:
            self._chunk_words = 0
            self._dma_depth = None
            self.g = GraphArrays(
                adj_bitmap=jnp.asarray(data.adj_bitmap),
                n_vertices=jnp.int32(data.n))
            self.adjacency_variant = "dense-vmem"
            self.adjacency_bytes = data.n * self.w * 4
        self.qb = QueryBank.empty(self.n_slots, self.w)
        self.tb = PatternStoreBank.empty(self.n_slots,
                                         self.pattern_capacity)
        self._empty_store = PatternStore.empty(
            self.pattern_capacity)                      # reused, immutable
        # cached [k]-stacked empty stores for burst admission (most
        # admissions carry no seed patterns — stacking on every burst
        # would cost seven dispatches per flush)
        self._empty_store_stacks: dict[int, PatternStore] = {}
        self.pool = SegmentPool(self.n_slots)
        self.queue: collections.deque[_Request] = collections.deque()
        self.finished: dict[int, MatchResult] = {}
        # per-query Δ snapshots (entries dicts, keep_table only)
        self.tables: dict[int, dict] = {}
        self._fresh_done: list[int] = []
        self._next_qid = 0
        self._rr = 0
        self._inflight: _Inflight | None = None
        # device-resident frontier stacks (DESIGN.md §2): plain
        # parallelism-1 queries keep their whole DFS stack in device
        # arrays and the host only sees per-slot scalar digests.
        # keep_table / parallelism>1 / single-step traffic stays on the
        # host SegmentPool path (it needs row-level introspection).
        self._use_device = (bool(opts.device_stacks)
                            and self.megastep_depth > 1)
        self.stack_capacity = tuned["stack_capacity"]
        # eager: the bank is a construction cost, not a first-query
        # latency cost (a fresh server's first batch used to pay it)
        self.sb: StackBank | None = (
            StackBank.empty(self.n_slots, self.stack_capacity, self.w)
            if self._use_device else None)
        self._inflight_dev: _InflightDev | None = None
        # aggregate wave statistics (for occupancy / SLO reporting)
        self.waves = 0
        self.rows_packed = 0
        self.occ_sum = 0.0
        self.waves_steady = 0
        self.occ_sum_steady = 0.0
        self.total_prunes = 0
        self.total_rows_created = 0
        self.total_steals = 0
        # per-slot work accounting (megastep digest lanes + host waves)
        self.slot_rows_expanded = np.zeros(self.n_slots, np.int64)
        self.slot_children_created = np.zeros(self.n_slots, np.int64)
        # host/device time split, each the sum of the spans (core.spans)
        # that name it: pack + async dispatch (host), blocked
        # materializing digests, digest processing / bookkeeping
        self.t_dispatch = Acc()
        self.t_sync = Acc()
        self.t_host = Acc()
        # host-time breakdown (disjoint buckets inside the above):
        # admission / digest fold / query retirement / pattern flush
        self.t_admit = Acc()
        self.t_flush = Acc()
        self.t_retire = Acc(self.t_flush)
        self.t_digest = Acc(self.t_retire, self.t_flush)
        # candidate filtering and ordering at submit (_prepare)
        self.t_prepare = Acc()
        self.prepared = 0
        # ---- fault tolerance (DESIGN.md §8) ---------------------------
        # every hook below is gated on its knob (or ``_faults is None``)
        # so the disabled path costs one attribute load per boundary
        self.dispatch_timeout_s = opts.dispatch_timeout_s
        self.dispatch_retries = int(opts.dispatch_retries)
        self.retry_backoff_s = float(opts.retry_backoff_s)
        self.validate_digests = bool(opts.validate_digests)
        self.fallback_on_failure = bool(opts.fallback_on_failure)
        self.max_query_failures = int(opts.max_query_failures)
        self.shed_policy = opts.shed_policy
        self._faults = opts.faults          # core.faults.FaultPlan | None
        self.fault_counters = {
            "dispatch_retries": 0, "hangs": 0, "digest_failures": 0,
            "quarantined": 0, "fallbacks": 0, "errors": 0,
            "flush_drops": 0, "shed": 0, "admission_failures": 0}

    # ------------------------------------------------------------------
    # submission / admission
    # ------------------------------------------------------------------
    def submit(self, query: Graph, *,
               options: MatchOptions | None = None,
               cand: list[np.ndarray] | None = None,
               order: np.ndarray | None = None,
               on_embeddings=None, on_admit=None, **overrides) -> int:
        """Enqueue a query; returns its scheduler query id.

        Per-query knobs (``limit``, ``time_budget_s``,
        ``max_recursions``/``max_rows``, ``use_pruning``,
        ``seed_patterns``, ``keep_table``, ``parallelism``,
        ``priority``) resolve through :class:`repro.api.MatchOptions`
        with this scheduler's ``options`` as the defaults — pass a full
        ``options`` object or keyword overrides.

        Raises :class:`QueueFull` when the bounded admission queue is at
        capacity — callers apply backpressure or shed load.

        ``parallelism``: intra-query shard count (shard-as-segments,
        DESIGN.md §3). The root-candidate range is split into that many
        root segments with per-shard DFS stacks and work stealing; all
        shards share the query's slot-private Δ table, so every pattern
        (μ > 0 included) one shard learns prunes the others.

        ``priority``: admission order from the bounded queue — higher
        admitted first, FIFO within a tie.

        ``on_embeddings``: streamed-delivery sink, called with each
        newly found ``[k, n_query]`` int32 batch as the emitting wave's
        digest is processed (not at retirement) — the plumbing behind
        ``MatchHandle.stream()``.

        ``on_admit``: called with no argument when the query first takes
        a slot (the end of its time in the queue).

        ``seed_patterns``: a pattern *entries* dict (patterns.store) to
        pre-load into the query's slot, hit counters included (cross-host
        pattern import or checkpoint restore — see core.distributed).
        μ > 0 seed patterns reference the *writer's* φ numbering: they
        are only sound if the ids cannot collide with this run's fresh
        ids — call :meth:`reserve_phi_floor` with the writer's φ ceiling
        first (checkpoint restore does), otherwise seed μ == 0 patterns
        only. Queries with no explicit seed may be warm-started from the
        cross-query template cache (μ == 0 entries only — sound without
        a floor).
        """
        opts = MatchOptions.resolve(
            options if options is not None else self.options, **overrides)
        if (len(self.queue) >= self.max_queue
                and self.shed_policy != "shed_lowest"):
            raise QueueFull(
                f"admission queue at capacity ({self.max_queue})")
        if query.n > N_PAD:
            raise ValueError(f"query too large for mask width: {query.n}")
        t_submit = time.perf_counter()
        qid = self._next_qid
        self._next_qid += 1
        with span(spans.SCHED_SUBMIT, query_id=qid):
            with span(spans.SCHED_PREPARE, self.t_prepare, query_id=qid):
                cand_by_pos, order, _pos_of, nbr_pos = _prepare(
                    query, self.data, cand, order)
            self.prepared += 1
            n = query.n
            v = self.data.n
            cand_dense = np.zeros((N_PAD, v), bool)
            for d in range(n):
                cand_dense[d, cand_by_pos[d]] = True
            nbr_mask = np.zeros((N_PAD, N_PAD), bool)
            qnbr_bits = np.zeros(N_PAD, np.uint64)
            for d in range(n):
                bits = np.uint64(0)
                for p in nbr_pos[d]:
                    nbr_mask[d, int(p)] = True
                    bits |= bit_of(int(p))
                qnbr_bits[d] = bits
            learn = (self.use_pruning if opts.use_pruning is None
                     else opts.use_pruning)
            cand_packed = pack_bitmap(cand_dense)
            req = _Request(
                query_id=qid, n=n, order=np.asarray(order, np.int32),
                roots=np.asarray(cand_by_pos[0], np.int32),
                cand_bitmap=cand_packed, nbr_mask=nbr_mask,
                qnbr_bits=qnbr_bits, limit=opts.limit, learn=learn,
                max_rows=opts.max_recursions,
                time_budget_s=opts.time_budget_s,
                seed_patterns=opts.seed_patterns, keep_table=opts.keep_table,
                t_submit=t_submit, fingerprint=None,
                parallelism=max(1, int(opts.parallelism)),
                priority=int(opts.priority), on_embeddings=on_embeddings,
                on_admit=on_admit)
            # trivial queries never need a slot (and never touch the cache)
            if len(req.roots) == 0 or n == 1:
                self._finish_trivial(req)
            else:
                # the fingerprint digests the packed candidate bitmap — not
                # free at web-scale V, so only queries that can actually
                # consume the cache (learning, cache enabled) pay for it
                if self.pattern_cache is not None and learn:
                    req.fingerprint = PatternCache.fingerprint(
                        n, cand_packed, nbr_mask)
                if len(self.queue) >= self.max_queue:
                    # shed_lowest overload policy: the overall lowest-
                    # priority request — queued or the new arrival, newest
                    # within a tie — completes immediately with
                    # status="shed" instead of growing the queue (or
                    # rejecting a high-priority arrival behind low traffic)
                    victim = min(range(len(self.queue)),
                                 key=lambda i: (self.queue[i].priority, -i))
                    if req.priority <= self.queue[victim].priority:
                        self._shed_request(req)
                        return qid
                    shed_req = self.queue[victim]
                    del self.queue[victim]
                    self._shed_request(shed_req)
                self.queue.append(req)
        return qid

    def _shed_request(self, req: _Request) -> None:
        """Finish a load-shed request: empty result, status "shed"."""
        stats = EngineStats()
        stats.aborted = True
        stats.abort_reason = "shed"
        stats.table_stats = None
        stats.wall_time_s = time.perf_counter() - req.t_submit
        self.finished[req.query_id] = MatchResult([], stats)
        self._fresh_done.append(req.query_id)
        self.fault_counters["shed"] += 1

    def _finish_trivial(self, req: _Request) -> None:
        stats = EngineStats()
        stats.table_stats = None
        embeddings: list[np.ndarray] = []
        if req.n == 1 and len(req.roots) > 0:
            stats.rows_created = len(req.roots)
            for v0 in req.roots:
                emb = np.empty(1, np.int32)
                emb[req.order[0]] = v0
                embeddings.append(emb)
            if req.limit is not None and len(embeddings) >= req.limit:
                embeddings = embeddings[:req.limit]
                stats.aborted = True
                stats.abort_reason = "limit"
            stats.found = len(embeddings)
            stats.recursions = stats.rows_created
        stats.wall_time_s = time.perf_counter() - req.t_submit
        if embeddings:
            stats.ttfe_s = stats.wall_time_s
            if req.on_embeddings is not None:
                req.on_embeddings(np.stack(embeddings).astype(np.int32))
        self.finished[req.query_id] = MatchResult(embeddings, stats)
        if req.keep_table:
            self.tables[req.query_id] = (req.seed_patterns
                                         if req.seed_patterns is not None
                                         else empty_entries())
        self._fresh_done.append(req.query_id)

    def reserve_phi_floor(self, floor: int) -> None:
        """Raise the pool's embedding-id counter to at least ``floor``.

        Makes seeding μ > 0 patterns sound: a seeded pattern fires only
        when a row's Φ[μ] equals its stored φ, and once every fresh id
        is above the writer's ceiling, a foreign φ can never collide
        with a live prefix id (it simply never matches again)."""
        self.pool.id_counter = max(self.pool.id_counter, int(floor))

    def _pop_admission(self) -> _Request:
        """Priority-aware pop from the bounded admission queue: the
        highest-priority request wins, FIFO within a tie (max over
        ``(priority, -index)``). O(queue) per admission — the queue is
        host-side and bounded by ``max_queue``."""
        best = max(range(len(self.queue)),
                   key=lambda i: (self.queue[i].priority, -i))
        req = self.queue[best]
        del self.queue[best]
        return req

    def _admit(self) -> None:
        # deferred slot installs: one fused load_slots / clear dispatch
        # for the whole admission burst instead of a per-query jit call
        # (a fresh batch of k queries used to pay k host dispatches of
        # ~0.3 ms each before the first wave could launch)
        loads: list[tuple] = []
        dev_clears: list[int] = []
        while self.queue:
            slot = self.pool.free_slot()
            if slot is None:
                break
            req = self._pop_admission()
            if self._faults is not None and self._faults.poke(
                    "admission", query_id=req.query_id) is not None:
                self.fault_counters["admission_failures"] += 1
                self._fail_request(req, "injected admission fault")
                continue
            learn = req.learn and self.pool.learning_enabled
            # Δ seed priority: explicit entries (restore / cross-host
            # import) > template-cache warm start (μ == 0 only, sound
            # without a φ floor) > empty store. Warm starts are gated on
            # ``learn`` so the no-pruning ablation stays pattern-free.
            entries = req.seed_patterns
            warm = False
            if entries is None and req.learn \
                    and self.pattern_cache is not None:
                pend = self._pending_snaps.pop(req.fingerprint, None)
                if pend is not None:
                    # the template recurred: materialize the deferred
                    # snapshot into its cache line now
                    snap_store, snap_hits = pend
                    with span(spans.SCHED_READBACK):
                        self.pattern_cache.put(
                            req.fingerprint,
                            store_to_entries(snap_store, snap_hits))
                entries = self.pattern_cache.get(req.fingerprint)
                warm = entries is not None
            if entries is not None and len(entries["pos"]) > 0:
                store = entries_to_store(entries, self.pattern_capacity)
            else:
                store = self._empty_store
            loads.append((slot, req.cand_bitmap, req.nbr_mask,
                          req.n, store, learn))
            now = time.perf_counter()
            deadline = (None if req.time_budget_s is None
                        else now + req.time_budget_s)
            q = QueryState(slot, req.query_id, req.n, req.order,
                           req.qnbr_bits, self.w, limit=req.limit,
                           learn=learn, max_rows=req.max_rows,
                           deadline=deadline, keep_table=req.keep_table,
                           t_submit=req.t_submit,
                           parallelism=req.parallelism)
            q.fingerprint = req.fingerprint
            q.emb_sink = req.on_embeddings
            # stash the request so a quarantined query can be replayed
            # on the fallback path (DESIGN.md §8)
            q.request = req
            q.fail_count = req.fail_count
            q.force_single = req.host_only
            if req.prior_embeddings:
                # degraded-mode replay: carry the embeddings found
                # before demotion; the replay deduplicates against
                # ``emb_seen`` so re-enumeration cannot double-count
                q.embeddings.extend(req.prior_embeddings)
                q.emb_delivered = len(req.prior_embeddings)  # streamed
                q.stats.found = len(req.prior_embeddings)
                q.stats.ttfe_s = req.prior_ttfe
            if req.host_only:
                q.emb_seen = req.emb_seen if req.emb_seen is not None \
                    else set()
                q.stats.rows_created += req.prior_rows
                q.stats.fallback = True
            q.stats.table_stats = DeadEndStats(
                capacity=self.pattern_capacity)
            if warm:
                q.stats.cache_hit = True
                q.stats.warm_patterns = len(entries["pos"])
                self.warm_started += 1
                self.warm_patterns_seeded += len(entries["pos"])
            if req.keep_table:
                q.hit_counts = {}
                if entries is not None:
                    for p, v, h in zip(entries["pos"].tolist(),
                                       entries["v"].tolist(),
                                       entries["hits"].tolist()):
                        q.hit_counts[(int(p), int(v))] = int(h)
            r = len(req.roots)
            q.stats.rows_created += r
            if (self._use_device and q.parallelism == 1
                    and not req.keep_table and not req.host_only):
                # device-resident stack path: no host segments — roots
                # trickle onto the device stack as it has headroom (the
                # cursor advances by the digest's per-slot accept count)
                q.device = True
                q.pending_roots = req.roots
                q.root_cursor = 0
                q.dev_roots_inflight = False
                q.dev_wedge = 0
                q.dev_sig = None
                if self.sb is None:
                    self.sb = StackBank.empty(
                        self.n_slots, self.stack_capacity, self.w)
                else:
                    dev_clears.append(slot)
            else:
                self._admit_host_roots(q, req.roots)
            self.pool.attach(slot, q)
            if req.on_admit is not None:
                req.on_admit()
        self._flush_slot_loads(loads, dev_clears)

    def _flush_slot_loads(self, loads: list[tuple],
                          dev_clears: list[int]) -> None:
        """Install an admission burst's bank rows in O(1) dispatches.

        Bursts are padded to the next power of two (pad rows carry slot
        index ``n_slots`` and are dropped by the scatter) so the number
        of distinct compiled shapes stays ``log2(n_slots) + 1`` per
        function instead of one compilation — and one dispatch — per
        admitted query."""
        if dev_clears:
            if len(dev_clears) == 1:
                self.sb = clear_slot_stack(self.sb,
                                           np.int32(dev_clears[0]))
            else:
                k = 1 << (len(dev_clears) - 1).bit_length()
                slots = np.full((k,), self.n_slots, np.int32)
                slots[:len(dev_clears)] = dev_clears
                self.sb = clear_slot_stacks(self.sb, slots)
        if not loads:
            return
        if len(loads) == 1:
            slot, cb, nm, n, store, learn = loads[0]
            self.qb, self.tb = load_slot(
                self.qb, self.tb, np.int32(slot), cb, nm,
                np.int32(n), store, learn)
            return
        k = 1 << (len(loads) - 1).bit_length()
        rows = loads + [loads[-1]] * (k - len(loads))
        slots = np.full((k,), self.n_slots, np.int32)
        slots[:len(loads)] = [r[0] for r in loads]
        if all(r[4] is self._empty_store for r in rows):
            store = self._empty_store_stacks.get(k)
            if store is None:
                store = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (k,) + x.shape),
                    self._empty_store)
                self._empty_store_stacks[k] = store
        else:
            store = jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[r[4] for r in rows])
        self.qb, self.tb = load_slots(
            self.qb, self.tb, slots,
            np.stack([np.asarray(r[1]) for r in rows]),
            np.stack([np.asarray(r[2]) for r in rows]),
            np.array([r[3] for r in rows], np.int32), store,
            np.array([r[5] for r in rows], bool))

    def _admit_host_roots(self, q: QueryState, all_roots: np.ndarray
                          ) -> None:
        """Seed host root segments (SegmentPool path). Shard-as-segments:
        one root segment per contiguous slice of the root-candidate range
        (``parallelism == 1`` keeps the single root segment of the
        classic schedule)."""
        r = len(all_roots)
        bounds = np.linspace(0, r, q.parallelism + 1).astype(int)
        for shard in range(q.parallelism):
            lo, hi = int(bounds[shard]), int(bounds[shard + 1])
            if hi <= lo:
                continue
            roots = all_roots[lo:hi]
            k = hi - lo
            frontier = np.full((k, N_PAD), -1, np.int32)
            frontier[:, 0] = roots
            used = np.zeros((k, self.w), np.uint32)
            used[np.arange(k), roots // 32] = (
                np.uint32(1) << (roots.astype(np.uint32)
                                 % np.uint32(32)))
            phi = np.zeros((k, N_PAD + 1), np.int32)
            base = self.pool.alloc_ids(k)
            phi[:, 1] = np.arange(base, base + k)
            root_seg = q.new_segment(1, frontier, used, phi,
                                     np.full(k, -1, np.int32),
                                     np.zeros(k, np.int32),
                                     shard=shard)
            q.push(WorkItem(root_seg.seg_id, 0, k, "fresh", shard))

    # ------------------------------------------------------------------
    # streamed-embedding delivery
    # ------------------------------------------------------------------
    def _deliver(self, q: QueryState) -> None:
        """Push embeddings found since the last delivery to the query's
        stream sink (and stamp TTFE on the first batch). Called from
        the digest-processing paths — ``_retire_mega`` and
        ``_process_wave`` — so consumers see embeddings while the query
        is still running, and once more from ``_finish`` as a flush."""
        n = len(q.embeddings)
        if n == q.emb_delivered:
            return
        if q.stats.ttfe_s is None:
            q.stats.ttfe_s = time.perf_counter() - q.t_submit
        if q.emb_sink is not None:
            batch = np.stack(q.embeddings[q.emb_delivered:]).astype(
                np.int32)
            q.emb_sink(batch)
        q.emb_delivered = n

    # ------------------------------------------------------------------
    # completion / abort / cancellation
    # ------------------------------------------------------------------
    def _finish(self, q: QueryState) -> None:
        with span(spans.SCHED_FINISH, self.t_retire,
                  query_id=q.query_id):
            self._deliver(q)
            q.materialize_hits()
            want_cache = (self.pattern_cache is not None and q.learn
                          and q.fingerprint is not None)
            if (q.keep_table or want_cache) and q.store_buf:
                # make patterns from the final resolutions visible in the
                # snapshot (distributed sharing / template cache)
                self._flush_stores(force=True)
            # materialize AFTER the final flush: the retiring query's last
            # insert counters must fold while it still owns its slot
            self._materialize_flush_counters()
            q.status = "done"
            q.evict()
            q.stats.recursions = q.stats.rows_created
            q.stats.wall_time_s = time.perf_counter() - q.t_submit
            if q.parallelism > 1:
                q.stats.shard_rows = q.shard_rows.tolist()
                q.stats.shard_items = q.shard_items.tolist()
            self.total_prunes += q.stats.deadend_prunes
            self.total_rows_created += q.stats.rows_created
            self.total_steals += q.stats.steals
            ts = q.stats.table_stats
            if isinstance(ts, DeadEndStats):
                # hits = Δ prunes; lookups stays 0 on the engine path
                # (see DeadEndStats — the digest has no lookup count)
                ts.hits = q.stats.deadend_prunes
            if q.keep_table:
                with span(spans.SCHED_READBACK):
                    entries = store_to_entries(
                        read_store_slot(self.tb, q.slot), q.hit_counts)
                if isinstance(ts, DeadEndStats):
                    ts.occupancy = len(entries["pos"])
                self.tables[q.query_id] = entries
                if want_cache:
                    # already materialized for the table export — fold the
                    # retiring learner's hot transferable patterns into the
                    # template's cache line right away
                    self.pattern_cache.put(q.fingerprint, entries)
            elif want_cache:
                # defer: capture the slot store as async device slices (no
                # pipeline stall here) — materialized into a cache line
                # only if the same template is admitted again
                snap = read_store_slot(self.tb, q.slot)
                hits = dict(q.hit_counts) if q.hit_counts is not None else None
                prev = self._pending_snaps.pop(q.fingerprint, None)
                if prev is not None:
                    # same template already has a pending snapshot (e.g. a
                    # richer earlier run): fold it into the cache line —
                    # put() merges by key — instead of discarding it
                    with span(spans.SCHED_READBACK):
                        self.pattern_cache.put(q.fingerprint,
                                               store_to_entries(*prev))
                self._pending_snaps[q.fingerprint] = (snap, hits)
                # tight bound: each pending snapshot pins a full-capacity
                # slice set on device (unlike the top_k-capped cache lines),
                # so size to the slot count, not to max_templates. An
                # LRU-evicted snapshot is materialized into its (compact)
                # cache line rather than discarded — otherwise interleaved
                # traffic over more templates than the pending bound would
                # never populate the cache at all.
                while len(self._pending_snaps) > max(8, 2 * self.n_slots):
                    old_fp, (old_snap, old_hits) = \
                        self._pending_snaps.popitem(last=False)
                    with span(spans.SCHED_READBACK):
                        self.pattern_cache.put(
                            old_fp, store_to_entries(old_snap, old_hits))
            self.finished[q.query_id] = MatchResult(q.embeddings, q.stats)
            self._fresh_done.append(q.query_id)
            if getattr(q, "device", False) and self.sb is not None:
                # release the slot's device stack; the clear chains in
                # program order after any in-flight dispatch (the handle is
                # that dispatch's output), so live entries cannot revive
                self.sb = clear_slot_stack(self.sb, np.int32(q.slot))
            self.pool.release(q.slot)

    def _abort(self, q: QueryState, reason: str) -> None:
        """Abort a query (budget exhausted or limit reached) and evict
        its segments; partial embeddings are kept. Rows of the query
        still in flight on the device are dropped at digest time."""
        q.stats.aborted = True
        q.stats.abort_reason = reason
        q.abort_reason = reason
        self._finish(q)

    def cancel(self, qid: int) -> bool:
        """Cancel a submitted query. A queued request is removed before
        it ever takes a slot; a resident query rides the existing
        abort/eviction path — its in-flight device rows are dropped at
        digest time and neighbors sharing its waves are untouched.
        Partial embeddings are kept (``abort_reason == "cancelled"``).
        Returns False when the query already finished."""
        if qid in self.finished:
            return False
        for i, req in enumerate(self.queue):
            if req.query_id == qid:
                del self.queue[i]
                stats = EngineStats()
                stats.aborted = True
                stats.abort_reason = "cancelled"
                stats.table_stats = None
                stats.wall_time_s = time.perf_counter() - req.t_submit
                self.finished[qid] = MatchResult([], stats)
                self._fresh_done.append(qid)
                return True
        for q in self.pool.active_queries():
            if q.query_id == qid:
                self._abort(q, "cancelled")
                return True
        return False

    # ------------------------------------------------------------------
    # fault tolerance: retry, quarantine, degraded-mode fallback
    # (DESIGN.md §8)
    # ------------------------------------------------------------------
    def _fail_request(self, req: _Request, msg: str) -> None:
        """Finish a request that failed before (or at) admission with
        ``status="error"``; any embeddings carried from a prior
        incarnation are kept."""
        stats = EngineStats()
        stats.aborted = True
        stats.abort_reason = "error"
        stats.fault = msg
        stats.table_stats = None
        stats.found = len(req.prior_embeddings or ())
        stats.wall_time_s = time.perf_counter() - req.t_submit
        self.finished[req.query_id] = MatchResult(
            list(req.prior_embeddings or ()), stats)
        self._fresh_done.append(req.query_id)
        self.fault_counters["errors"] += 1

    def _run_dispatch(self, call, queries: list, stacks: bool):
        """Run one device dispatch with bounded retry + exponential
        backoff. Returns ``(result, hung)``; ``result is None`` means
        the retry budget is exhausted — the involved ``queries`` have
        been quarantined and the device banks rebuilt (``stacks=True``
        additionally rebuilds the frontier StackBank). An injected hang
        runs the dispatch but flags its digest untrusted for the
        retire-side watchdog."""
        attempt = 0
        while True:
            hung = False
            try:
                if self._faults is not None:
                    spec = self._faults.poke("dispatch")
                    if spec is not None:
                        if spec.kind == "hang":
                            self.fault_counters["hangs"] += 1
                            hung = True
                        else:
                            raise FaultInjected(
                                "injected dispatch exception")
                return call(), hung
            except DISPATCH_ERRORS as exc:
                attempt += 1
                if attempt > self.dispatch_retries:
                    self._dispatch_failed(queries, exc, stacks)
                    return None, False
                self.fault_counters["dispatch_retries"] += 1
                time.sleep(self.retry_backoff_s * (2 ** (attempt - 1)))

    def _dispatch_failed(self, queries: list, exc: BaseException,
                         stacks: bool) -> None:
        msg = (f"dispatch failed after {self.dispatch_retries + 1} "
               f"attempts: {exc}")
        self._invalidate_device_state(stacks)
        for q in list(queries):
            if q.active:
                self._quarantine(q, msg)

    def _invalidate_device_state(self, stacks: bool) -> None:
        """Rebuild the device banks after a hang / failed dispatch /
        globally-invalid digest. Always sound: Δ patterns only ever
        prune (losing them costs pruning, never correctness) and every
        query whose frontier stack lived in the bank is quarantined by
        the caller before the rebuild, so no live state is dropped."""
        self.tb = PatternStoreBank.empty(self.n_slots,
                                         self.pattern_capacity)
        self._flush_ctr_dev = None
        self._pending_snaps.clear()
        if stacks and self._use_device:
            self.sb = StackBank.empty(self.n_slots, self.stack_capacity,
                                      self.w)

    def _quarantine(self, q: QueryState, reason: str) -> None:
        """Quarantine state machine: resident → quarantined →
        fallback re-admission on the host/single-step path, or — past
        the per-query failure budget (or with fallback disabled) —
        errored through the existing abort/eviction path."""
        self.fault_counters["quarantined"] += 1
        q.fail_count += 1
        req = q.request
        if (self.fallback_on_failure and req is not None
                and q.fail_count <= self.max_query_failures):
            self.fault_counters["fallbacks"] += 1
            self._demote_to_host(q, req, reason)
        else:
            self.fault_counters["errors"] += 1
            q.stats.fault = reason
            self._abort(q, "error")

    def _demote_to_host(self, q: QueryState, req: _Request,
                        reason: str) -> None:
        """Tear the query down *without* publishing a result and
        re-enqueue its original request on the host single-step
        fallback path (``host_only``: no device stack, one item per
        wave). Embeddings found so far ride along and the replay
        deduplicates against them, so the final set is exact; neighbors
        are untouched — their rows never leave their own slots."""
        seen = set()
        prior = []
        for e in q.embeddings:
            b = np.asarray(e, np.int32)
            key = b.tobytes()
            if key not in seen:
                seen.add(key)
                prior.append(b)
        req2 = dataclasses.replace(
            req, host_only=True, fail_count=q.fail_count,
            prior_embeddings=prior, emb_seen=seen,
            prior_rows=q.stats.rows_created, prior_ttfe=q.stats.ttfe_s,
            seed_patterns=None, on_embeddings=q.emb_sink)
        q.status = "quarantined"    # in-flight digests for this slot drop
        q.evict()
        if q.device and self.sb is not None:
            self.sb = clear_slot_stack(self.sb, np.int32(q.slot))
        self.pool.release(q.slot)
        # internal re-admission: jumps the max_queue bound (the query
        # already held a slot) and front-runs its priority tie
        self.queue.appendleft(req2)

    def _validate_device_digest(self, dig: dict, n_emb: int,
                                embS: np.ndarray, embF: np.ndarray,
                                slot_map: dict) -> tuple[dict, bool]:
        """Check every invariant a sound digest must satisfy (see
        DESIGN.md §8 for why each is implied by Lemma 1/4 soundness).
        Returns ``(bad, global_bad)`` — ``bad`` maps a failing slot to
        the violated invariant; ``global_bad`` flags corruption that
        cannot be blamed on one slot (the whole digest is dropped)."""
        cap = self.stack_capacity
        v = self.data.n
        if n_emb < 0 or n_emb > self._emb_cap:
            return {}, True
        if n_emb and ((embS < 0) | (embS >= self.n_slots)).any():
            return {}, True
        bad: dict[int, str] = {}
        for slot, q in slot_map.items():
            if not q.active or not q.device:
                continue
            pend, live = int(dig["d_pending"][slot]), \
                int(dig["d_live"][slot])
            if not (0 <= pend <= live <= cap):
                bad[slot] = (f"stack occupancy out of bounds: "
                             f"pending={pend} live={live} capacity={cap}")
                continue
            neg = [k for k in ("d_accepted", "d_expanded", "d_rows",
                               "d_prunes", "d_inj", "d_stored")
                   if int(dig[k][slot]) < 0]
            if neg:
                bad[slot] = f"negative counter lane {neg[0]}"
                continue
            if int(dig["d_outsum"][slot]) != int(dig["d_childlive"][slot]):
                bad[slot] = (
                    "Lemma-4 outstanding-counter conservation violated: "
                    f"sum(outstanding)={int(dig['d_outsum'][slot])} != "
                    f"live children={int(dig['d_childlive'][slot])}")
                continue
            if n_emb:
                rows = embF[embS == slot][:, :q.n]
                if len(rows) and ((rows < 0) | (rows >= v)).any():
                    bad[slot] = "embedding row vertex out of range"
        return bad, False

    def _fold_embeddings(self, q: QueryState, rows: np.ndarray
                         ) -> np.ndarray:
        """Fold a ``[k, >= q.n]`` batch of found embedding rows into the
        query: permute to query-vertex order, deduplicate against a
        fallback replay's carried set, apply the limit, stream. Returns
        a bool mask marking rows that must count as *reported* (they
        produced a valid embedding — duplicates included, so Lemma-1/4
        resolution can never learn a failure pattern from a successful
        row). Rows clipped by the limit stay unmarked: the caller
        aborts on the limit immediately after, so they are never
        resolved as failures."""
        k = len(rows)
        out = np.empty((k, q.n), np.int32)
        out[:, q.order[:q.n]] = rows[:, :q.n]
        if q.emb_seen is None:
            accept = np.ones(k, bool)
        else:
            accept = np.fromiter(
                (r.tobytes() not in q.emb_seen for r in out),
                bool, count=k)
        take = int(accept.sum())
        if q.limit is not None:
            take = min(take, q.limit - q.stats.found)
        report = np.ones(k, bool)
        idx = np.nonzero(accept)[0]
        report[idx[max(0, take):]] = False
        if take > 0:
            idx = idx[:take]
            if q.emb_seen is not None:
                for i in idx:
                    q.emb_seen.add(out[i].tobytes())
            q.embeddings.extend(out[idx])
            q.stats.found += take
            self._deliver(q)           # stream before retirement
        return report

    def _reset_learning_on_overflow(self) -> None:
        """Embedding-id overflow: clear all stores and pause learning
        (sound — only pruning is lost); the pool re-enables learning
        once it drains. Shared by both schedule paths."""
        if self.pool.id_overflow and self.pool.learning_enabled:
            self.tb = PatternStoreBank.empty(self.n_slots,
                                             self.pattern_capacity)
            self.pool.learning_enabled = False
            for qq in self.pool.active_queries():
                qq.learn = False

    def _check_budgets(self, now: float | None = None) -> None:
        for q in self.pool.active_queries():
            if q.deadline is not None:
                if now is None:
                    now = time.perf_counter()
                if now > q.deadline:
                    self._abort(q, "time")
                    continue
            if q.max_rows is not None and q.stats.rows_created > q.max_rows:
                self._abort(q, "rows")

    # ------------------------------------------------------------------
    # wave packing
    # ------------------------------------------------------------------
    def _pack_wave(self
                   ) -> list[tuple[QueryState, Segment, int, int, int]] | None:
        """Fill one wave with ready rows, round-robin across queries.

        All picks share one kind ("fresh" or "leftover") because the two
        run different device programs; a query whose ready items are all
        of the other kind simply waits for a later wave.

        Occupancy-aware packing: the classic one-work-item-per-query
        round-robin is the *fair-share floor*, not a ceiling. On the
        fused megastep schedule a query may contribute up to
        ``max(1, wave_size / n_active)`` items per wave, so a lone heavy
        query fills the wave instead of idling rows. The synchronous
        single-step schedule (``megastep_depth == 1`` or the prune-EMA
        fallback) keeps the strict one-item cadence — in failure-heavy
        regimes patterns learned from one slice must prune the next
        slice of the same query, which multi-item packing would defeat.

        Within a query, items are drawn round-robin across its shard
        stacks (shard-as-segments), after rebalancing idle shards via
        work stealing. Returns [(query, segment, start, stop, shard)] or
        None when no work exists.
        """
        active = self.pool.active_queries()
        if not active:
            return None
        for q in active:
            if q.parallelism > 1:
                q.balance_shards()
        start = self._rr % len(active)
        order = active[start:] + active[:start]
        self._rr += 1
        if (self.megastep_depth <= 1
                or self._prune_ema > self.adaptive_prune_threshold):
            item_cap = 1
        else:
            item_cap = max(1, self.wave_size // len(active))
        kind = None
        picks: list[tuple[QueryState, Segment, int, int, int]] = []
        remaining = self.wave_size
        taken = dict.fromkeys(range(len(order)), 0)
        progress = True
        while remaining > 0 and progress:
            progress = False
            for qi, q in enumerate(order):
                if remaining == 0:
                    break
                # fallback queries keep the strict single-item cadence
                # regardless of the engine-wide packing mode
                if taken[qi] >= (1 if q.force_single else item_cap):
                    continue
                if kind is None:
                    kind = q.peek_kind()
                    if kind is None:
                        continue
                item = q.pop_ready(kind)
                if item is None:
                    taken[qi] = item_cap     # nothing of this kind now
                    continue
                take = min(remaining, item.stop - item.start)
                if take < item.stop - item.start:
                    q.push(WorkItem(item.seg_id, item.start + take,
                                    item.stop, item.kind, item.shard))
                picks.append((q, q.segments[item.seg_id], item.start,
                              item.start + take, item.shard))
                remaining -= take
                taken[qi] += 1
                progress = True
        if not picks:
            return None
        self._wave_kind = kind
        return picks

    def _build_wave(self, picks: list, kind: str):
        """Pack picked segment slices into fixed-shape wave arrays."""
        f_pad = self.wave_size
        fr = np.full((f_pad, N_PAD), -1, np.int32)
        us = np.zeros((f_pad, self.w), np.uint32)
        ph = np.zeros((f_pad, N_PAD + 1), np.int32)
        lo = np.zeros((f_pad, self.w), np.uint32)
        valid = np.zeros(f_pad, bool)
        slot_v = np.zeros(f_pad, np.int32)
        depth_v = np.zeros(f_pad, np.int32)
        metas: list[tuple[QueryState, Segment, int, int, int, int, int]] = []
        off = 0
        for q, seg, s, e, shard in picks:
            k = e - s
            fr[off:off + k] = seg.frontier[s:e]
            us[off:off + k] = seg.used[s:e]
            ph[off:off + k] = seg.phi[s:e]
            valid[off:off + k] = ~seg.resolved[s:e]
            slot_v[off:off + k] = q.slot
            depth_v[off:off + k] = seg.depth
            if kind == "leftover":
                lo[off:off + k] = seg.pending_leftover[s:e]
            metas.append((q, seg, s, e, off, k, shard))
            off += k
        self.waves += 1
        self.rows_packed += off
        occ = off / f_pad
        self.occ_sum += occ
        if self.pool.n_active == self.n_slots:
            self.waves_steady += 1
            self.occ_sum_steady += occ
        return fr, us, ph, lo, valid, slot_v, depth_v, metas

    def _note_prunes(self, prunes: int, rows: int) -> None:
        """Feed one wave's prune/row counts into the adaptive-depth EMA
        (decay 0.5: ~5 easy waves flip a cold scheduler to deep mode, a
        single prune-heavy wave flips it back)."""
        rate = prunes / max(1, prunes + rows)
        self._prune_ema = 0.5 * self._prune_ema + 0.5 * rate

    # ------------------------------------------------------------------
    # pattern store flushing
    # ------------------------------------------------------------------
    def _pending_stores(self) -> list[tuple[QueryState, list]]:
        return [(q, q.store_buf) for q in self.pool.active_queries()
                if q.store_buf]

    @staticmethod
    def _drain_dedup(bufs: list, max_take: int | None) -> dict:
        """Drain up to ``max_take`` queued (key_pos, key_v, φ, μ, Γ)
        tuples from per-query buffers, deduplicated by (slot, key): the
        device insert is last-write-wins per key anyway, and one wave of
        a failure-heavy query queues the same key many times — host
        dedup shrinks the device batch ~4x on the trap workload.
        Consumed entries are removed from the buffers."""
        dedup: dict = {}
        i = 0
        for q, buf in bufs:
            take = (len(buf) if max_take is None
                    else min(len(buf), max_take - i))
            for key_pos, key_v, phi_id, mu_len, gamma in buf[:take]:
                dedup[(q.slot, key_pos, key_v)] = (phi_id, mu_len, gamma)
            i += take
            del buf[:take]
            if max_take is not None and i == max_take:
                break
        return dedup

    @staticmethod
    def _pack_store_batch(dedup: dict, n_pad: int):
        """Pack deduplicated entries into padded insert arrays (the
        validity lane marks padding; the device insert drops invalid
        rows)."""
        slots = np.zeros(n_pad, np.int32)
        kpos = np.zeros(n_pad, np.int32)
        kv = np.zeros(n_pad, np.int32)
        phis = np.zeros(n_pad, np.int32)
        mus = np.zeros(n_pad, np.int32)
        masks = np.zeros(n_pad, np.uint64)
        valid = np.zeros(n_pad, bool)
        for i, ((slot, key_pos, key_v), (phi_id, mu_len, gamma)) \
                in enumerate(dedup.items()):
            slots[i] = slot
            kpos[i] = key_pos
            kv[i] = key_v
            phis[i] = phi_id
            mus[i] = mu_len
            masks[i] = gamma
            valid[i] = True
        return slots, kpos, kv, phis, mus, words_from64(masks), valid

    def _fold_store_counters(self, counters, slot_map: dict | None) -> None:
        """Fold per-slot device insert counters (int32 [S] lanes) into
        the scheduler totals and the owning queries' DeadEndStats."""
        with span(spans.SCHED_READBACK):
            lanes = {"stored": np.asarray(counters[0], np.int64),
                     "overwrites": np.asarray(counters[1], np.int64),
                     "evictions": np.asarray(counters[2], np.int64),
                     "dropped": np.asarray(counters[3], np.int64)}
        for k, v in lanes.items():
            self.store_counters[k] += int(v.sum())
        if slot_map is None:
            slot_map = {q.slot: q for q in self.pool.active_queries()}
        for slot, q in slot_map.items():
            ts = q.stats.table_stats
            if not isinstance(ts, DeadEndStats):
                continue
            ts.stores += int(lanes["stored"][slot])
            ts.overwrites += int(lanes["overwrites"][slot])
            ts.evictions += int(lanes["evictions"][slot])
            ts.dropped += int(lanes["dropped"][slot])

    def _flush_stores(self, force: bool = False) -> None:
        """Standalone batched Δ insert (single-step path and forced
        flushes). Skips the dispatch entirely when nothing is pending,
        and below ``store_flush_min`` unless forced; arrays are padded
        to power-of-two buckets so the jitted insert compiles O(log)
        variants instead of one per distinct batch length."""
        bufs = self._pending_stores()
        if not bufs:
            return
        with span(spans.STORE_FLUSH, self.t_flush):
            if not self.pool.learning_enabled:
                for q, buf in bufs:
                    buf.clear()
                return
            total = sum(len(buf) for _, buf in bufs)
            if not force and total < self.store_flush_min:
                return
            dedup = self._drain_dedup(bufs, None)
            if self._faults is not None and dedup and self._faults.poke(
                    "flush", n=len(dedup)) is not None:
                # injected flush failure: drop the batch — sound, patterns
                # only ever prune
                self.fault_counters["flush_drops"] += 1
                return
            n_pad = 16
            while n_pad < len(dedup):
                n_pad *= 2
            self.tb, counters = store_patterns_mq(
                self.tb, *self._pack_store_batch(dedup, n_pad))
            self._flush_ctr_dev = (counters if self._flush_ctr_dev is None
                                   else self._flush_ctr_dev.add(counters))

    def _materialize_flush_counters(self) -> None:
        """Fold the accumulated flush counters into stats. Correct
        per-query attribution holds because this runs at every
        ownership-change point (each query finish), so between two folds
        every slot has a single owner."""
        if self._flush_ctr_dev is None:
            return
        ctr, self._flush_ctr_dev = self._flush_ctr_dev, None
        self._fold_store_counters(ctr, None)

    def _drain_store_batch(self):
        """Drain up to ``store_pad`` host-queued pattern stores into the
        fixed-length arrays that ride the next megastep dispatch.
        Leftover entries stay queued for the next wave."""
        with span(spans.STORE_FLUSH, self.t_flush):
            bufs = self._pending_stores()
            if not self.pool.learning_enabled:
                for q, buf in bufs:
                    buf.clear()
                bufs = []
            dedup = self._drain_dedup(bufs, self.store_pad)
            if self._faults is not None and dedup and self._faults.poke(
                    "flush", n=len(dedup)) is not None:
                # injected flush failure: drop the pattern batch (sound)
                self.fault_counters["flush_drops"] += 1
                dedup = {}
            return self._pack_store_batch(dedup, self.store_pad)

    # ------------------------------------------------------------------
    # one scheduling step (double-buffered pipeline)
    # ------------------------------------------------------------------
    @spans.traced(spans.SCHED_STEP)
    def step(self) -> bool:
        """Admit, pack, and execute one wave. Returns False when idle.

        With ``megastep_depth > 1`` the wave is dispatched as a fused
        megastep and the *previous* dispatch's digest is processed only
        after the new one is in flight — host bookkeeping overlaps
        device compute (double buffering).
        """
        self._check_budgets()
        with span(spans.SCHED_ADMIT, self.t_admit):
            self._admit()
        if self.waves - self._last_aged_wave >= self.hit_decay_every:
            # age the device hit counters so eviction ranks *recent*
            # usefulness (stale hot entries decay back into candidates);
            # runs on every schedule path, single-step included
            self.tb = age_hits(self.tb)
            self._last_aged_wave = self.waves
        if self.megastep_depth <= 1:
            return self._step_single()
        ema_high = self._prune_ema > self.adaptive_prune_threshold
        # device-resident pipeline: dispatched before any host-side
        # digest processing so device compute overlaps it. Under a high
        # prune EMA the dispatch runs with t_max=1 (traced, no
        # recompile) — the paper's tight store→lookup cadence.
        retired_dev = False
        if self._inflight_dev is not None and self._device_tail():
            # tail regime (every root already on device): retire the
            # in-flight call *before* dispatching, so a pool that just
            # completed skips the speculative trailing dispatch — at
            # tail the lost dispatch/retire overlap is worth less than
            # a wasted fixed-cost device call
            sync_dev, self._inflight_dev = self._inflight_dev, None
            self._retire_device(sync_dev)
            retired_dev = True
        with span(spans.SCHED_DISPATCH_DEVICE, self.t_dispatch):
            rec_dev = self._dispatch_device(
                1 if ema_high else self.megastep_depth)
        prev_dev, self._inflight_dev = self._inflight_dev, rec_dev
        if ema_high:
            # failure-heavy regime: drain the pipeline and fall back to
            # the synchronous single-step schedule so every wave sees
            # the patterns learned from the one before it.
            prev, self._inflight = self._inflight, None
            if prev is not None:
                if prev.kind == "mega":
                    self._retire_mega(prev)
                else:
                    self._retire_leftover(prev)
            progressed = self._step_single() or prev is not None
        else:
            with span(spans.SCHED_DISPATCH_WAVE, self.t_dispatch):
                picks = self._pack_wave()
                rec: _Inflight | None = None
                if picks is not None:
                    if self._wave_kind == "fresh":
                        rec = self._dispatch_mega(picks)
                    else:
                        rec = self._dispatch_leftover(picks)
            prev, self._inflight = self._inflight, rec
            if prev is not None:
                if prev.kind == "mega":
                    self._retire_mega(prev)
                else:
                    self._retire_leftover(prev)
            progressed = prev is not None or rec is not None
        if prev_dev is not None:
            self._retire_device(prev_dev)
        return (progressed or retired_dev or prev_dev is not None
                or rec_dev is not None)

    # ------------------------------------------------------------------
    # device-resident stack dispatch / retire (DESIGN.md §2)
    # ------------------------------------------------------------------
    def _device_tail(self) -> bool:
        """True when every device query's roots are already on device —
        there is nothing left to feed, so dispatches only continue the
        device-resident expansion/resolution."""
        if self.queue:
            return False             # queued admissions bring new roots
        devq = [q for q in self.pool.active_queries()
                if getattr(q, "device", False)]
        return bool(devq) and not any(
            len(q.pending_roots) > q.root_cursor for q in devq)

    def _dispatch_device(self, t_max: int) -> _InflightDev | None:
        """Dispatch one device-resident scheduling step: feed pending
        roots into slots with headroom and let the device repack, expand
        and resolve up to ``t_max`` waves from its per-slot stacks. The
        host never sees rows — only the per-slot scalar digest."""
        devq = [q for q in self.pool.active_queries()
                if getattr(q, "device", False)]
        if not devq or self.sb is None:
            return None
        devq.sort(key=lambda q: q.slot)      # _group_rank wants slot order
        # root intake is wider than the wave: a fresh batch's roots land
        # in one dispatch instead of trickling across several
        f = 2 * self.wave_size
        in_root = np.full(f, -1, np.int32)
        in_rid = np.zeros(f, np.int32)
        in_slot = np.zeros(f, np.int32)
        in_valid = np.zeros(f, bool)
        active = np.zeros(self.n_slots, bool)
        root_slots = []
        off = 0
        for q in devq:
            active[q.slot] = True
            if q.dev_roots_inflight:
                continue                     # previous batch unacked
            rest = len(q.pending_roots) - q.root_cursor
            if rest <= 0 or off >= f:
                continue
            k = min(rest, f - off)
            roots = q.pending_roots[q.root_cursor:q.root_cursor + k]
            base = self.pool.alloc_ids(k)
            in_root[off:off + k] = roots
            in_rid[off:off + k] = np.arange(base, base + k,
                                            dtype=np.int32)
            in_slot[off:off + k] = q.slot
            in_valid[off:off + k] = True
            q.dev_roots_inflight = True
            root_slots.append(q.slot)
            off += k
        if t_max > 1 and off == 0 and not any(
                len(q.pending_roots) > q.root_cursor for q in devq):
            # tail regime: every root is already on device, so there is
            # no admission granularity left to preserve — deepen the
            # call to amortize its fixed dispatch cost (t_max is traced,
            # so this changes no compilation)
            t_max = 2 * t_max
        # worst-case fresh-id reservation for the in-loop allocations —
        # reserving up front keeps the dispatch fully async
        id_base = self.pool.alloc_ids(t_max * f * self._mega_kpr)
        self._reset_learning_on_overflow()
        res, hung = self._run_dispatch(
            lambda: run_device_megastep(
                self.g, self.qb, self.tb, self.sb, in_root, in_rid,
                in_slot, in_valid, active, np.int32(id_base),
                bool(self.pool.learning_enabled), np.int32(t_max),
                kpr=self._mega_kpr, emb_cap=self._emb_cap,
                backend=self._kernel_backend, wave=self.wave_size,
                block_f=self._block_f, dma_depth=self._dma_depth),
            devq, stacks=True)
        if res is None:
            return None                      # retries exhausted: the
        self.tb = res.tb                     # queries were quarantined
        self.sb = res.sb                     # handles only — not
        # wave/occupancy/EMA accounting happens at retire time, where
        # the digest says whether the wave actually carried work — the
        # trailing empty dispatches that detect completion must not
        # dilute occupancy or decay the adaptive-depth EMA
        return _InflightDev(res, {q.slot: q for q in devq},
                            tuple(root_slots), t_max,
                            t_dispatch=time.perf_counter(), hung=hung)

    def _watchdog_fire(self, slot_map: dict, msg: str,
                       stacks: bool) -> None:
        """A hung or untrusted dispatch retires cleanly instead of
        blocking all slots: rebuild the device banks and quarantine
        every involved query (each restarts on the fallback path or
        errors out past its failure budget)."""
        self._invalidate_device_state(stacks)
        for q in list(slot_map.values()):
            if q.active:
                self._quarantine(q, msg)

    @spans.traced(spans.SCHED_RETIRE_DEVICE)
    def _retire_device(self, rec: _InflightDev) -> None:
        """Fold one device-resident digest: per-slot scalars into query
        stats (no per-row lanes exist), the embedding batch out to the
        owning queries, then completion / budget / wedge checks."""
        if rec.hung:
            # injected hang: neither the digest nor the banks it chains
            # from are trusted — don't even materialize it
            self._watchdog_fire(rec.slot_map, "injected dispatch hang",
                                stacks=True)
            return
        res = rec.res
        with span(spans.SCHED_READBACK, self.t_sync):
            dig = {k: np.asarray(getattr(res, k)) for k in _DEV_LANES}
            n_emb = max(0, min(int(res.n_emb), self._emb_cap))
            embF = np.asarray(res.emb_frontier)[:n_emb]
            embS = np.asarray(res.emb_slot)[:n_emb]
        with span(spans.SCHED_DIGEST, (self.t_host, self.t_digest)):
            if (self.dispatch_timeout_s is not None
                    and time.perf_counter() - rec.t_dispatch
                    > self.dispatch_timeout_s):
                # per-dispatch watchdog: the call blocked past its deadline
                # — whatever it returned is not worth trusting over a clean
                # restart of the involved queries
                self.fault_counters["hangs"] += 1
                self._watchdog_fire(
                    rec.slot_map, "dispatch exceeded watchdog deadline "
                    f"({self.dispatch_timeout_s:g}s)", stacks=True)
                return
            if self._faults is not None:
                slots = sorted(s for s, q in rec.slot_map.items()
                               if q.active and q.device)
                spec = (self._faults.poke("digest", slots=slots)
                        if slots else None)
                if spec is not None:
                    dig = {k: np.array(v) for k, v in dig.items()}
                    corrupt_digest(dig, spec,
                                   stack_capacity=self.stack_capacity,
                                   slots=slots)
            if self.validate_digests:
                bad, global_bad = self._validate_device_digest(
                    dig, int(res.n_emb), embS, embF, rec.slot_map)
                if global_bad:
                    self.fault_counters["digest_failures"] += 1
                    self._watchdog_fire(rec.slot_map,
                                        "device digest globally invalid",
                                        stacks=True)
                    return
                if bad:
                    # quarantine each failing slot's query and zero its
                    # lanes/rows so the aggregate folds below stay clean —
                    # neighbors' digests (and embedding rows) are untouched
                    dig = {k: (v if v.flags.writeable else v.copy())
                           for k, v in dig.items()}
                    for slot, why in bad.items():
                        self.fault_counters["digest_failures"] += 1
                        q = rec.slot_map[slot]
                        for k in _DEV_LANES:
                            dig[k][slot] = 0
                        if q.active:
                            self._quarantine(
                                q, f"digest validation failed: {why}")
                    if len(embS):
                        keep = ~np.isin(embS, list(bad))
                        embF, embS = embF[keep], embS[keep]
            n_emb = len(embS)
            d_accepted = dig["d_accepted"]
            d_expanded = dig["d_expanded"]
            d_rows = dig["d_rows"]
            d_prunes = dig["d_prunes"]
            d_inj = dig["d_inj"]
            d_stored = dig["d_stored"]
            d_pending = dig["d_pending"]
            d_live = dig["d_live"]

            self._fold_store_counters(
                (res.pat_stored, res.pat_overwrites, res.pat_evictions,
                 res.pat_dropped), rec.slot_map)
            self.slot_rows_expanded += d_expanded.astype(np.int64)
            self.slot_children_created += d_rows.astype(np.int64)
            expanded_total = int(d_expanded.sum())
            worked = bool(expanded_total or n_emb or d_accepted.sum())
            if worked:
                self.rows_packed += expanded_total
                occ = min(1.0, expanded_total / (self.wave_size * rec.t_max))
                self.occ_sum += occ
                self.waves += 1
                for q in rec.slot_map.values():
                    if q.active:
                        q.stats.waves += 1
                if self.pool.n_active == self.n_slots:
                    self.waves_steady += 1
                    self.occ_sum_steady += occ

            emb_per_slot = (np.bincount(embS, minlength=self.n_slots)
                            if n_emb else np.zeros(self.n_slots, np.int64))

            # ---- per-query scalar digest fold ------------------------------
            for slot, q in rec.slot_map.items():
                if not q.active or not getattr(q, "device", False):
                    continue
                q.stats.rows_created += int(d_rows[slot])
                q.stats.deadend_prunes += int(d_prunes[slot])
                q.stats.injectivity_fails += int(d_inj[slot])
                q.stats.patterns_stored += int(d_stored[slot])
                if q.dev_roots_inflight and slot in rec.root_slots:
                    q.root_cursor += int(d_accepted[slot])
                    q.dev_roots_inflight = False

            # ---- embeddings found on device (+ limit aborts) ---------------
            if n_emb:
                for sl_v in np.unique(embS):
                    q = rec.slot_map.get(int(sl_v))
                    if q is None or not q.active:
                        continue
                    self._fold_embeddings(q, embF[embS == sl_v])
                    if q.limit is not None and q.stats.found >= q.limit:
                        self._abort(q, "limit")

            # ---- completion / budget / wedge checks ------------------------
            for slot, q in rec.slot_map.items():
                if not q.active or not getattr(q, "device", False):
                    continue
                if (q.max_rows is not None
                        and q.stats.rows_created > q.max_rows):
                    self._abort(q, "rows")
                    continue
                roots_done = (q.root_cursor >= len(q.pending_roots)
                              and not q.dev_roots_inflight)
                if (roots_done and d_pending[slot] == 0
                        and d_live[slot] == 0):
                    # done — any embedding batch that landed this retire was
                    # already streamed above (the embedding fold runs before
                    # this loop), so consumers observe delivery-then-done
                    # within the same retire and no trailing empty dispatch
                    # is needed to finish the query
                    self._finish(q)
                    continue
                # wedge detection: a full stack can throttle to a state
                # where iterations select rows but nothing allocates,
                # resolves, embeds or stores. After 3 observably identical
                # digests, export the stack back to host segments.
                moved = (int(d_accepted[slot]) or int(d_rows[slot])
                         or int(emb_per_slot[slot]) or int(d_stored[slot])
                         or int(d_prunes[slot]))
                sig = (int(d_pending[slot]), int(d_live[slot]))
                if moved or sig != q.dev_sig:
                    q.dev_wedge = 0
                else:
                    q.dev_wedge += 1
                q.dev_sig = sig
                if q.dev_wedge >= 3:
                    self._export_device_query(q)
            if worked:
                self._note_prunes(int(d_prunes.sum()), int(d_rows.sum()))

    @spans.traced(spans.SCHED_EXPORT)
    def _export_device_query(self, q: QueryState) -> None:
        """Wedge fallback: materialize one slot's device stack back into
        host segments (one 1-row segment per live entry, parent links
        preserved) and route the query through the SegmentPool path from
        here on. Rare — only when the bounded stack throttles into a
        no-progress state — and exact: entry lanes carry the identical
        Lemma-4 bookkeeping the host keeps."""
        slot = q.slot
        if self._inflight_dev is not None:
            # the in-flight dispatch's mutations are already in the
            # materialized stack (program order): ack its root batch now
            # and drop its digest for this query at retire time
            if (q.dev_roots_inflight
                    and slot in self._inflight_dev.root_slots):
                with span(spans.SCHED_READBACK):
                    q.root_cursor += int(np.asarray(
                        self._inflight_dev.res.d_accepted)[slot])
        q.dev_roots_inflight = False
        q.device = False
        sb = self.sb
        with span(spans.SCHED_READBACK):
            st = np.asarray(sb.state[slot])
            frontier = np.asarray(sb.frontier[slot])
            used = np.asarray(sb.used[slot])
            phi = np.asarray(sb.phi[slot])
            depth = np.asarray(sb.depth[slot])
            cand = np.asarray(sb.cand[slot])
            gamma64 = mask64(np.asarray(sb.gamma[slot]))
            outstanding = np.asarray(sb.outstanding[slot])
            reported = np.asarray(sb.reported[slot])
            parent = np.asarray(sb.parent[slot])
        live = np.nonzero(st != STK_FREE)[0]
        seg_of: dict[int, Segment] = {}
        for e in live.tolist():
            seg = q.new_segment(
                int(depth[e]), frontier[e:e + 1].copy(),
                used[e:e + 1].copy(), phi[e:e + 1].copy(),
                np.full(1, -1, np.int32), np.zeros(1, np.int32))
            seg_of[e] = seg
        res_items: list = []
        for e in live.tolist():
            seg = seg_of[e]
            p = int(parent[e])
            if p >= 0 and p in seg_of:
                seg.parent_seg[0] = seg_of[p].seg_id
                seg.parent_row[0] = 0
            state = int(st[e])
            if state == STK_FRESH:
                q.push(WorkItem(seg.seg_id, 0, 1, "fresh", 0))
                continue
            seg.expanded[0] = True
            seg.gamma[0] = gamma64[e]
            seg.outstanding[0] = int(outstanding[e])
            seg.reported[0] = bool(reported[e])
            if state == STK_LEFT:
                seg.pending_leftover[0] = cand[e]
                q.push(WorkItem(seg.seg_id, 0, 1, "leftover", 0))
            elif state == STK_RES:
                # already finalized on device (pattern stored there)
                seg.stored[0] = True
                res_items.append((seg.seg_id, 0, bool(reported[e]),
                                  gamma64[e]))
            elif state == STK_WAIT and int(outstanding[e]) == 0:
                res_items.append(q.finalize_row(seg, 0))
        q.resolve_rows(res_items)
        rest = q.pending_roots[q.root_cursor:]
        if len(rest):
            self._admit_host_roots(q, rest)
            q.stats.rows_created -= len(rest)   # counted at admission
        q.root_cursor = len(q.pending_roots)
        self.sb = clear_slot_stack(self.sb, np.int32(slot))
        if not q.segments:
            self._finish(q)

    # ------------------------------------------------------------------
    # megastep dispatch / retire
    # ------------------------------------------------------------------
    def _dispatch_mega(self, picks: list) -> _Inflight:
        fr, us, ph, _lo, valid, slot_v, depth_v, metas = \
            self._build_wave(picks, "fresh")
        st = self._drain_store_batch()
        # worst-case id reservation: every ring position beyond the
        # input wave is a fresh row. Reserving up front lets the next
        # dispatch go out before this digest is read.
        id_base = self.pool.alloc_ids(self._ring_capacity - self.wave_size)
        self._reset_learning_on_overflow()
        res, hung = self._run_dispatch(
            lambda: run_megastep_mq(
                self.g, self.qb, self.tb, fr, us, ph, valid, slot_v,
                depth_v, *st, np.int32(id_base),
                bool(self.pool.learning_enabled),
                kpr=self._mega_kpr, k_depth=self.megastep_depth,
                capacity=self._ring_capacity, emb_cap=self._emb_cap,
                backend=self._kernel_backend, block_f=self._block_f,
                dma_depth=self._dma_depth),
            list({q.slot: q for q, *_ in metas}.values()), stacks=False)
        if res is None:
            return None             # retries exhausted: queries demoted
        self.tb = res.tb            # handle only — not materialized
        for q in {q.slot: q for q, *_ in metas}.values():
            q.stats.waves += 1
        # slot map over ALL dispatch-time owners, not just the wave's
        # picks: the drained store batch carries buffered patterns from
        # every active query, so digest counter attribution must too
        slot_map = {q.slot: q for q in self.pool.active_queries()}
        return _Inflight("mega", res, metas, slot_map,
                         t_dispatch=time.perf_counter(), hung=hung)

    @spans.traced(spans.SCHED_RETIRE_WAVE)
    def _retire_mega(self, rec: _Inflight) -> None:
        if rec.hung:
            self._watchdog_fire(
                {q.slot: q for q, *_ in rec.metas},
                "injected dispatch hang", stacks=False)
            return
        res: MegaResult = rec.res
        with span(spans.SCHED_READBACK, self.t_sync):
            head = int(res.head)
            tail = int(res.tail)
            bufF = np.asarray(res.buf_frontier)
            bufU = np.asarray(res.buf_used)
            bufP = np.asarray(res.buf_phi)
            slot_a = np.asarray(res.buf_slot)
            depth_a = np.asarray(res.buf_depth)
            parent_a = np.asarray(res.buf_parent)
            valid_a = np.asarray(res.buf_valid)
            rempty = np.asarray(res.refined_empty)
            nchild = np.asarray(res.n_children)
            nleft = np.asarray(res.n_leftover)
            leftover = np.asarray(res.leftover)
            pmask = mask64(np.asarray(res.partial_mask))
            nprun = np.asarray(res.n_pruned)
            ninj = np.asarray(res.n_inj)
            nembr = np.asarray(res.n_emb_row)
            dstored = np.asarray(res.dev_stored)
            pruned_v = np.asarray(res.pruned_v)
            n_emb = int(res.n_emb)
            embF = np.asarray(res.emb_frontier)[:max(0, n_emb)]
            embS = np.asarray(res.emb_slot)[:max(0, n_emb)]
        with span(spans.SCHED_DIGEST, (self.t_host, self.t_digest)):
            if (self.dispatch_timeout_s is not None
                    and time.perf_counter() - rec.t_dispatch
                    > self.dispatch_timeout_s):
                self.fault_counters["hangs"] += 1
                self._watchdog_fire({q.slot: q for q, *_ in rec.metas},
                                    "dispatch exceeded watchdog deadline "
                                    f"({self.dispatch_timeout_s:g}s)",
                                    stacks=False)
                return
            if self.validate_digests and not (
                    0 <= head <= tail <= self._ring_capacity
                    and 0 <= n_emb <= self._emb_cap):
                # the ring digest has no per-slot blame: an out-of-bounds
                # head/tail invalidates the whole dispatch
                self.fault_counters["digest_failures"] += 1
                self._watchdog_fire(
                    {q.slot: q for q, *_ in rec.metas},
                    f"megastep digest globally invalid (head={head} "
                    f"tail={tail} n_emb={n_emb})", stacks=False)
                return

            # ---- Δ store accounting (digest counter lanes) -----------------
            self._fold_store_counters(
                (res.pat_stored, res.pat_overwrites, res.pat_evictions,
                 res.pat_dropped), rec.slot_map)

            f_in = self.wave_size
            slot_map = rec.slot_map
            involved: dict[int, QueryState] = {}
            sweeps: dict[int, list] = {}
            # per-slot work accounting surfaced by the digest
            with span(spans.SCHED_READBACK):
                self.slot_rows_expanded += np.asarray(res.slot_rows,
                                                      np.int64)
                self.slot_children_created += np.asarray(
                    res.slot_children, np.int64)
            # shard of every ring row: input rows from their pick's work
            # item, in-loop rows inherit their parent's shard (parents
            # always precede children, so K passes reach every chain)
            shard_of = np.zeros(tail, np.int32)

            # ---- 1) input-row bookkeeping (rows [0, f_in) of the ring) -----
            for q, seg, s, e, woff, k, shard in rec.metas:
                shard_of[woff:woff + k] = shard
                if not q.active:
                    continue
                involved[q.query_id] = q
                sl = slice(woff, woff + k)
                rows = slice(s, e)
                seg.gamma[rows] |= pmask[sl]
                seg.pending_leftover[rows] = leftover[sl]
                seg.expanded[rows] = True
                seg.stored[rows] |= dstored[sl]
                seg.outstanding[rows] += nchild[sl]
                seg.reported[rows] |= nembr[sl] > 0
                q.stats.deadend_prunes += int(nprun[sl].sum())
                q.stats.injectivity_fails += int(ninj[sl].sum())
                q.stats.patterns_stored += int(dstored[sl].sum())
                if (nleft[sl] > 0).any():
                    q.push(WorkItem(seg.seg_id, s, e, "leftover", shard))
                sweeps.setdefault(q.query_id, []).append(
                    (seg, np.arange(s, e), rempty[sl]))

            # ---- Δ hit counters (pruned-child lanes, any ring row) ---------
            if any(q.hit_counts is not None for q in slot_map.values()):
                for sl_v, q in slot_map.items():
                    if q.hit_counts is None:
                        continue
                    rows = np.nonzero(slot_a[:tail] == sl_v)[0]
                    if len(rows):
                        q.note_hits(depth_a[rows], pruned_v[rows])

            # ---- 2) embeddings found in-loop (+ limit aborts) --------------
            if n_emb:
                for sl_v in np.unique(embS):
                    q = slot_map.get(int(sl_v))
                    if q is None or not q.active:
                        continue
                    self._fold_embeddings(q, embF[embS == sl_v])
                    if q.limit is not None and q.stats.found >= q.limit:
                        self._abort(q, "limit")

            # ---- 3) rows created in-loop -> new segments -------------------
            if tail > f_in:
                # ring index -> (q-local segment id, row) for parent links;
                # parents always precede children in the ring.
                seg_of = np.full(tail, -1, np.int64)
                row_of = np.full(tail, -1, np.int64)
                for q, seg, s, e, woff, k, shard in rec.metas:
                    seg_of[woff:woff + k] = seg.seg_id
                    row_of[woff:woff + k] = np.arange(s, e)
                new_idx = np.arange(f_in, tail)
                new_idx = new_idx[valid_a[f_in:tail]]
                # propagate shards down parent chains (≤ K links deep) —
                # skipped on the default path where every shard id is 0
                if any(q.parallelism > 1 for q in slot_map.values()):
                    for _ in range(self.megastep_depth):
                        shard_of[new_idx] = shard_of[parent_a[new_idx]]
                sl_arr = slot_a[new_idx]
                for sl_v in np.unique(sl_arr):
                    q = slot_map.get(int(sl_v))
                    qsel = new_idx[sl_arr == sl_v]
                    if q is None or not q.active:
                        continue
                    involved[q.query_id] = q
                    qd = depth_a[qsel]
                    qsh = shard_of[qsel]
                    for d_v in np.unique(qd):          # ascending: parents
                        dsel = qsel[qd == d_v]         # precede children
                        dsh = qsh[qd == d_v]
                        for sh_v in np.unique(dsh):    # segments stay
                            sel = dsel[dsh == sh_v]    # shard-pure
                            exp_sel = sel[sel < head]
                            sel2 = np.concatenate([exp_sel, sel[sel >= head]])
                            r = len(sel2)
                            n_exp = len(exp_sel)
                            q.stats.rows_created += r
                            cseg = q.new_segment(
                                int(d_v), bufF[sel2], bufU[sel2], bufP[sel2],
                                seg_of[parent_a[sel2]].astype(np.int32),
                                row_of[parent_a[sel2]].astype(np.int32),
                                shard=int(sh_v))
                            cseg.expanded[:n_exp] = True
                            cseg.gamma[:n_exp] = pmask[exp_sel]
                            cseg.pending_leftover[:] = leftover[sel2]
                            cseg.outstanding[:] = nchild[sel2]
                            cseg.reported[:] = nembr[sel2] > 0
                            cseg.stored[:] = dstored[sel2]
                            q.stats.deadend_prunes += int(nprun[exp_sel].sum())
                            q.stats.injectivity_fails += int(
                                ninj[exp_sel].sum())
                            q.stats.patterns_stored += int(dstored[sel2].sum())
                            seg_of[sel2] = cseg.seg_id
                            row_of[sel2] = np.arange(r)
                            if n_exp < r:
                                q.push(WorkItem(cseg.seg_id, n_exp, r, "fresh",
                                                int(sh_v)))
                            if n_exp and (nleft[exp_sel] > 0).any():
                                q.push(WorkItem(cseg.seg_id, 0, n_exp,
                                                "leftover", int(sh_v)))
                            sweeps.setdefault(q.query_id, []).append(
                                (cseg, np.arange(n_exp), rempty[exp_sel]))

            # ---- 4) Lemma-4 resolution sweep over every expanded row -------
            for qid, q in involved.items():
                if not q.active:
                    continue
                items: list = []
                for seg, srows, remask in sweeps.get(qid, []):
                    if seg.seg_id not in q.segments:
                        continue
                    unres = ~seg.resolved[srows]
                    for row in srows[remask & unres]:
                        # Lemma 1: Γ = N(u_d) ∩ dom(M̂)
                        gam = q.qnbr_bits[seg.depth] & below(seg.depth)
                        items.append((seg.seg_id, int(row), False, gam))
                    cand = srows[~remask & unres]
                    if len(cand):
                        done = cand[(seg.outstanding[cand] == 0)
                                    & seg.expanded[cand]
                                    & ~seg.pending_leftover[cand].any(axis=1)]
                        for row in done:
                            if seg.reported[row]:
                                items.append((seg.seg_id, int(row), True,
                                              np.uint64(0)))
                            else:
                                items.append(q.finalize_row(seg, int(row)))
                q.resolve_rows(items)
                if (q.max_rows is not None
                        and q.stats.rows_created > q.max_rows):
                    self._abort(q, "rows")
                elif not q.segments:
                    self._finish(q)
            self._note_prunes(int(nprun[:tail].sum()), max(0, tail - f_in))

    # ------------------------------------------------------------------
    # leftover extraction dispatch / retire (single-step program)
    # ------------------------------------------------------------------
    def _dispatch_leftover(self, picks: list) -> _Inflight:
        fr, us, ph, lo, valid, slot_v, depth_v, metas = \
            self._build_wave(picks, "leftover")
        res = extract_more_mq(self.tb, ph, slot_v, depth_v, lo,
                              kpr=4 * self.kpr)
        self.tb = res[7]            # handle with hit counters bumped
        slot_map = {q.slot: q for q, *_ in metas}
        for q in slot_map.values():
            q.stats.waves += 1
        return _Inflight("leftover", res, metas, slot_map,
                         fr=fr, us=us, ph=ph, depth_v=depth_v)

    @spans.traced(spans.SCHED_RETIRE_WAVE)
    def _retire_leftover(self, rec: _Inflight) -> None:
        res = rec.res
        with span(spans.SCHED_READBACK, self.t_sync):
            child_v = np.asarray(res[0])
            child_valid = np.asarray(res[1])
            leftover = np.asarray(res[2])
            n_leftover = np.asarray(res[3])
            partial = mask64(np.asarray(res[4]))
            n_pruned = np.asarray(res[5])
            pruned_v = np.asarray(res[6])
        with span(spans.SCHED_DIGEST, (self.t_host, self.t_digest)):
            f_pad = self.wave_size
            digest = dict(
                refined_empty=np.zeros(f_pad, bool),
                n_children=child_valid.sum(axis=1).astype(np.int32),
                n_leftover=n_leftover, partial=partial, child_v=child_v,
                child_valid=child_valid, leftover=leftover,
                n_pruned=n_pruned, n_inj=np.zeros(f_pad, np.int32),
                pruned_v=pruned_v)
            self._process_wave("leftover", rec.metas, rec.fr, rec.us, rec.ph,
                               rec.depth_v, digest)

    # ------------------------------------------------------------------
    # single-step wave processing (megastep_depth == 1 reference path,
    # and the leftover-extraction retire)
    # ------------------------------------------------------------------
    def _step_single(self) -> bool:
        picks = self._pack_wave()
        if picks is None:
            return False
        kind = self._wave_kind
        with span(spans.SCHED_DISPATCH_WAVE, self.t_dispatch):
            fr, us, ph, lo, valid, slot_v, depth_v, metas = \
                self._build_wave(picks, kind)
            self._flush_stores()
            for q in {q.slot: q for q, *_ in metas}.values():
                q.stats.waves += 1
            if kind == "fresh":
                self.slot_rows_expanded += np.bincount(
                    slot_v[valid], minlength=self.n_slots).astype(np.int64)
                res, self.tb = expand_wave_mq(
                    self.g, self.qb, self.tb, fr, us, ph, valid, slot_v,
                    depth_v, kpr=self.kpr, backend=self._kernel_backend,
                    block_f=self._block_f, dma_depth=self._dma_depth)
            else:
                res = extract_more_mq(self.tb, ph, slot_v, depth_v, lo,
                                      kpr=4 * self.kpr)
                self.tb = res[7]    # handle with hit counters bumped
        with span(spans.SCHED_RETIRE_WAVE):
            with span(spans.SCHED_READBACK, self.t_sync):
                if kind == "fresh":
                    digest = dict(
                        refined_empty=np.asarray(res.refined_empty),
                        n_children=np.asarray(res.n_children),
                        n_leftover=np.asarray(res.n_leftover),
                        partial=mask64(np.asarray(res.partial_mask)),
                        child_v=np.asarray(res.child_v),
                        child_valid=np.asarray(res.child_valid),
                        leftover=np.asarray(res.leftover),
                        n_pruned=np.asarray(res.n_pruned),
                        n_inj=np.asarray(res.n_inj),
                        pruned_v=np.asarray(res.pruned_v))
                else:
                    child_valid = np.asarray(res[1])
                    digest = dict(
                        refined_empty=np.zeros(self.wave_size, bool),
                        n_children=child_valid.sum(axis=1).astype(np.int32),
                        n_leftover=np.asarray(res[3]),
                        partial=mask64(np.asarray(res[4])),
                        child_v=np.asarray(res[0]), child_valid=child_valid,
                        leftover=np.asarray(res[2]),
                        n_pruned=np.asarray(res[5]),
                        n_inj=np.zeros(self.wave_size, np.int32),
                        pruned_v=np.asarray(res[6]))
            with span(spans.SCHED_DIGEST, (self.t_host, self.t_digest)):
                self._process_wave(kind, metas, fr, us, ph, depth_v, digest)
        return True

    def _process_wave(self, kind: str, metas: list, fr, us, ph, depth_v,
                      digest: dict) -> None:
        """Host bookkeeping for one single-step wave digest: child
        assembly, embedding extraction, Lemma-4 resolution."""
        f_pad = self.wave_size
        refined_empty = digest["refined_empty"]
        n_children = digest["n_children"]
        n_leftover = digest["n_leftover"]
        partial = digest["partial"]
        child_v = digest["child_v"]
        child_valid = digest["child_valid"]
        leftover = digest["leftover"]
        n_pruned = digest["n_pruned"]
        n_inj = digest["n_inj"]
        pruned_v = digest["pruned_v"]

        # mask out rows of evicted queries (aborted while this wave was
        # in flight) and last-level rows — their children are
        # embeddings, not rows.
        last_level = np.zeros(f_pad, bool)
        dead_rows = np.zeros(f_pad, bool)
        for q, seg, s, e, woff, k, shard in metas:
            if seg.depth + 1 == q.n:
                last_level[woff:woff + k] = True
            if not q.active:
                dead_rows[woff:woff + k] = True
        child_valid_eff = child_valid & ~last_level[:, None] \
            & ~dead_rows[:, None]

        cf = cu = cp = par = cvalid = None
        if child_valid_eff.any():
            id_base = self.pool.alloc_ids(int(child_valid_eff.sum()))
            cf, cu, cp, par, cvalid = assemble_children_mq(
                fr, us, ph, np.where(child_valid_eff, child_v, -1),
                child_valid_eff, depth_v, np.int32(id_base))
            with span(spans.SCHED_READBACK):
                cf = np.asarray(cf)
                cu = np.asarray(cu)
                cp = np.asarray(cp)
                par = np.asarray(par)
                cvalid = np.asarray(cvalid)
            self._reset_learning_on_overflow()

        # ---- per-item host bookkeeping ---------------------------------
        wave_rows_created = 0
        for q, seg, s, e, woff, k, shard in metas:
            if not q.active:
                continue
            sl = slice(woff, woff + k)
            rows = slice(s, e)
            seg.gamma[rows] |= partial[sl]
            seg.pending_leftover[rows] = leftover[sl]
            q.stats.deadend_prunes += int(n_pruned[sl].sum())
            if q.hit_counts is not None:
                q.note_hits(depth_v[sl], pruned_v[sl])
            if kind == "fresh":
                seg.expanded[rows] = True
                q.stats.injectivity_fails += int(n_inj[sl].sum())

            # re-queue leftover before children (LIFO: children first)
            if (n_leftover[sl] > 0).any():
                q.push(WorkItem(seg.seg_id, s, e, "leftover", shard))

            item_last = seg.depth + 1 == q.n
            if item_last:
                # complete embeddings (vectorized gather + permute)
                emb_rows, emb_cols = np.nonzero(child_valid[sl])
                if len(emb_rows):
                    mrows = seg.frontier[s + emb_rows].copy()
                    mrows[:, seg.depth] = \
                        child_v[woff + emb_rows, emb_cols]
                    report = self._fold_embeddings(q, mrows)
                    seg.reported[s + emb_rows[report]] = True
                if q.limit is not None and q.stats.found >= q.limit:
                    self._abort(q, "limit")
                    continue
            else:
                seg.outstanding[rows] += n_children[sl]
                # compact this item's children into a new segment
                if (n_children[sl] > 0).any():
                    lo_f, hi_f = woff * child_v.shape[1], \
                        (woff + k) * child_v.shape[1]
                    sel = np.nonzero(cvalid[lo_f:hi_f])[0] + lo_f
                    n_new = len(sel)
                    q.stats.rows_created += n_new
                    wave_rows_created += n_new
                    self.slot_children_created[q.slot] += n_new
                    cseg = q.new_segment(
                        seg.depth + 1, cf[sel], cu[sel], cp[sel],
                        np.full(n_new, seg.seg_id, np.int32),
                        (par[sel] - woff + s).astype(np.int32),
                        shard=shard)
                    q.push(WorkItem(cseg.seg_id, 0, n_new, "fresh", shard))

            # immediate resolutions
            items = []
            for i in range(k):
                row = s + i
                if seg.resolved[row]:
                    continue
                if refined_empty[woff + i]:
                    # Lemma 1: Γ = N(u_d) ∩ dom(M̂)
                    gam = q.qnbr_bits[seg.depth] & below(seg.depth)
                    items.append((seg.seg_id, row, False, gam))
                elif (seg.outstanding[row] == 0 and seg.expanded[row]
                      and not seg.pending_leftover[row].any()):
                    if seg.reported[row]:
                        items.append((seg.seg_id, row, True, np.uint64(0)))
                    else:
                        items.append(q.finalize_row(seg, row))
            q.resolve_rows(items)

            if q.max_rows is not None and q.stats.rows_created > q.max_rows:
                self._abort(q, "rows")
            elif not q.segments:
                self._finish(q)
        self._note_prunes(int(n_pruned.sum()), wave_rows_created)

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------
    def poll(self) -> list[int]:
        """Query ids completed since the last poll."""
        done, self._fresh_done = self._fresh_done, []
        return done

    @property
    def idle(self) -> bool:
        return (not self.queue and self.pool.n_active == 0
                and self._inflight is None
                and self._inflight_dev is None)

    def run(self) -> dict[int, MatchResult]:
        """Drain all queued and in-flight queries; returns the finished
        map (also available as ``self.finished``)."""
        while self.step():
            pass
        return self.finished

    def scheduler_stats(self) -> dict:
        """Aggregate wave statistics for SLO / occupancy reporting.
        Prune/row totals include still-active queries, so mid-run polling
        sees live numbers."""
        with span(spans.METRICS_READBACK):
            self._materialize_flush_counters()
            occupancy = np.asarray(self.tb.valid.sum(axis=1), np.int64)
        prunes = self.total_prunes + sum(
            q.stats.deadend_prunes for q in self.pool.active_queries())
        rows = self.total_rows_created + sum(
            q.stats.rows_created for q in self.pool.active_queries())
        steals = self.total_steals + sum(
            q.stats.steals for q in self.pool.active_queries())
        return {
            "steals": steals,
            "slot_rows_expanded": self.slot_rows_expanded.tolist(),
            "slot_children_created": self.slot_children_created.tolist(),
            "waves": self.waves,
            "rows_packed": self.rows_packed,
            "wave_size": self.wave_size,
            "n_slots": self.n_slots,
            "megastep_depth": self.megastep_depth,
            "mean_occupancy": (self.occ_sum / self.waves
                               if self.waves else 0.0),
            "steady_occupancy": (self.occ_sum_steady / self.waves_steady
                                 if self.waves_steady else 0.0),
            "steady_waves": self.waves_steady,
            "peak_active": self.pool.peak_active,
            "queued": len(self.queue),
            "active": self.pool.n_active,
            "deadend_prunes": prunes,
            "rows_created": rows,
            "prune_rate": prunes / max(1, prunes + rows),
            "dispatch_time_s": self.t_dispatch.s,
            "device_sync_time_s": self.t_sync.s,
            "host_time_s": self.t_host.s,
            # disjoint host-time breakdown: where host wall actually
            # goes — digest folding, admission, retirement (_finish),
            # Δ pattern flushing
            "host_admission_time_s": self.t_admit.s,
            "host_digest_time_s": self.t_digest.s,
            "host_retirement_time_s": self.t_retire.s,
            "host_flush_time_s": self.t_flush.s,
            # candidate filtering and ordering at submit, and the queries
            # that paid it
            "host_prepare_time_s": self.t_prepare.s,
            "prepared": self.prepared,
            "device_stacks": self._use_device,
            # adjacency layout (DESIGN.md §2): which refine variant this
            # engine compiled ("dense-vmem" | "hier-hbm") and what the
            # resident adjacency costs — the scale bench's headline
            "adjacency_variant": self.adjacency_variant,
            "adjacency_bytes": self.adjacency_bytes,
            "chunk_words": self._chunk_words,
            # bounded hashed Δ store + cross-query template cache
            # (occupancy reads the live bank so every schedule path —
            # single-step included — reports real store pressure)
            "pattern_capacity": self.pattern_capacity,
            "store_stored": self.store_counters["stored"],
            "store_overwrites": self.store_counters["overwrites"],
            "store_evictions": self.store_counters["evictions"],
            "store_dropped": self.store_counters["dropped"],
            "store_occupancy": occupancy.tolist(),
            "store_load_factor": float(
                occupancy.max() / self.pattern_capacity
                if self.n_slots else 0.0),
            "warm_started": self.warm_started,
            "warm_patterns_seeded": self.warm_patterns_seeded,
            # fault-tolerance counters (DESIGN.md §8): retries, hangs,
            # digest validation failures, quarantines and their
            # outcomes (fallback vs error), flush drops, load shedding
            "faults": dict(self.fault_counters),
            # the tuning record this scheduler resolved at construction
            # (DESIGN.md §9) — "tuning-cache" names the consumed
            # TUNING_CACHE.json record, "builtin" means defaults
            "tuning": dict(self.tuning_record),
            "pattern_cache": (self.pattern_cache.report()
                              if self.pattern_cache is not None else None),
        }


class WaveEngine:
    """Single-query facade over the request/handle API (one slot).

    A thin compatibility wrapper (DESIGN.md §4): ``match`` submits a
    :class:`repro.api.MatchRequest` through a one-slot
    :class:`repro.api.MatchSession` and blocks on the handle. Use the
    session/handle API directly for async submit, streaming, and
    cancellation.

    Usage::

        eng = WaveEngine(data_graph)
        res = eng.match(query_graph, limit=1000)
    """

    def __init__(self, data: Graph, *,
                 options: MatchOptions | None = None, **knobs):
        from ..api.session import MatchSession   # deferred: layering
        knobs["n_slots"] = 1                     # the single-query facade
        self._session = MatchSession(
            data, options=MatchOptions.resolve(options, **knobs))
        self.scheduler = self._session.scheduler

    def match(self, query: Graph, *,
              options: MatchOptions | None = None,
              cand: list[np.ndarray] | None = None,
              order: np.ndarray | None = None,
              **overrides) -> MatchResult:
        """Blocking single-query match; knobs resolve through
        :class:`repro.api.MatchOptions` (``seed_patterns`` follows
        :meth:`WaveScheduler.submit`'s μ > 0 soundness rule;
        ``parallelism`` is the intra-query shard count)."""
        h = self._session.submit(query, options=options, cand=cand,
                                 order=order, keep_table=True,
                                 **overrides)
        qr = h.result()
        self._entries = self.scheduler.tables.pop(h.query_id, None)
        return MatchResult(qr.embeddings, qr.stats)


def match_vectorized(query: Graph, data: Graph,
                     **knobs) -> MatchResult:
    """One-shot convenience wrapper around :class:`WaveEngine`: every
    per-query and per-engine knob is a :class:`repro.api.MatchOptions`
    field (``limit``, ``use_pruning``, ``wave_size``, ``kpr``,
    ``megastep_depth``, ``pattern_capacity``, …)."""
    opts = MatchOptions.resolve(None, **knobs)
    return WaveEngine(data, options=opts).match(query, options=opts)
