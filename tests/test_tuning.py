"""Autotuner tests (DESIGN.md §9): search-space validity, cache
round-trip + staleness, the resolution precedence (explicit arg >
MatchOptions > tuning cache > built-in default), and the oracle-equality
pin under a deliberately weird tuned configuration."""
import json

import numpy as np
import pytest

from repro.api.options import ENGINE_TUNABLE_DEFAULTS, MatchOptions
from repro.core.backtrack import backtrack_deadend
from repro.core.vectorized import WaveScheduler
from repro.data.graph_gen import (corridor_graph, er_labeled_graph,
                                  random_walk_query, trap_graph)
from repro.kernels import config as kconfig
from repro.tuning import (CandidateConfig, TunableSpace, TuningCache,
                          WorkloadShape, cache_key, device_kind,
                          quantize_vertices, resolve_engine_options,
                          schema_hash)
from repro.tuning.space import PROBE, refine_vmem_bytes


def embset(embeddings):
    return set(frozenset(enumerate(np.asarray(e).tolist()))
               for e in embeddings)


# --------------------------------------------------------- search space
def test_probe_pin_matches_pattern_store():
    """space.PROBE is a literal copy of the store's probe window (kept
    so tuning/ imports without the patterns package) — they must agree
    or the capacity floor stops meaning 'one probe sequence fits'."""
    from repro.patterns.store import PROBE as STORE_PROBE
    assert PROBE == STORE_PROBE


def test_space_rejects_invalid_points_before_compile():
    """Every constraint fires as a reason string from pure shape
    arithmetic — an invalid point is never handed to the engine (the
    enumeration below never imports jax)."""
    shape = WorkloadShape.for_graph(128)
    space = TunableSpace("jnp", shape)
    assert space.validate(CandidateConfig()) is None

    r = space.validate(CandidateConfig(wave_size=48))
    assert r is not None and "power of two" in r
    r = space.validate(CandidateConfig(pattern_capacity=4))
    assert r is not None and "probe window" in r
    r = space.validate(CandidateConfig(stack_capacity=256, wave_size=512))
    assert r is not None and "stack_capacity" in r
    r = space.validate(CandidateConfig(megastep_depth=0))
    assert r is not None and ">= 1" in r

    # hierarchical layout knobs: C must be a power of two in [1, 128]
    # (C=1 is the degenerate-but-legal one-word-chunk layout)
    assert space.validate(CandidateConfig(chunk_words=1)) is None
    r = space.validate(CandidateConfig(chunk_words=3))
    assert r is not None and "chunk_words" in r and "power of two" in r
    r = space.validate(CandidateConfig(chunk_words=256))
    assert r is not None and "chunk_words" in r
    r = space.validate(CandidateConfig(dma_depth=0))
    assert r is not None and ">= 1" in r
    r = space.validate(CandidateConfig(hbm_adjacency=2))
    assert r is not None and "hbm_adjacency" in r

    # block_f tiling: only the compiled pallas backend demands the
    # sublane multiple — interpret and jnp accept odd heights
    odd = CandidateConfig(block_f=12)
    r = TunableSpace("pallas", shape).validate(odd)
    assert r is not None and "sublane" in r
    assert TunableSpace("pallas_interpret", shape).validate(odd) is None
    assert TunableSpace("jnp", shape).validate(odd) is None

    # VMEM budget: a graph whose padded adjacency bitmap alone exceeds
    # the budget rejects every block height with the byte arithmetic
    big = WorkloadShape.for_graph(200_000)
    assert refine_vmem_bytes(big, 8) > TunableSpace(
        "pallas", big).vmem_budget_bytes
    r = TunableSpace("pallas", big).validate(CandidateConfig())
    assert r is not None and "VMEM" in r
    # ... which is exactly the regime the hierarchical layout exists
    # for: the same shape passes when the adjacency stays in HBM and
    # only the paging scratch must fit
    assert TunableSpace("pallas", big).validate(
        CandidateConfig(hbm_adjacency=1)) is None


def test_space_enumeration_partitions_cross_product():
    space = TunableSpace("pallas", WorkloadShape.for_graph(128))
    domains = {"block_f": [4, 8], "megastep_depth": [2, 6],
               "wave_size": [64], "n_slots": [8],
               "stack_capacity": [1024], "pattern_capacity": [4, 1024],
               "store_flush_min": [16], "hbm_adjacency": [0],
               "chunk_words": [8], "dma_depth": [2]}
    valid = space.candidates(overrides=domains)
    assert len(valid) + len(space.rejected) == 2 * 2 * 2
    # block_f=4 (sublane) and pattern_capacity=4 (probe floor) are out
    assert len(valid) == 2
    assert all(c.block_f == 8 and c.pattern_capacity == 1024
               for c in valid)
    with pytest.raises(KeyError, match="warp_factor"):
        space.candidates(overrides={"warp_factor": [1]})


def test_smoke_domains_contain_default_point():
    """The smoke sweep must include the built-in-default point so the
    recorded best is structurally never worse than the defaults."""
    from repro.tuning.autotune import SMOKE_DOMAINS
    d = CandidateConfig(wave_size=64)        # smoke pins the packing
    for k in ("block_f", "megastep_depth", "stack_capacity",
              "pattern_capacity", "store_flush_min"):
        assert getattr(d, k) in SMOKE_DOMAINS[k]


# ---------------------------------------------------------------- cache
def test_cache_roundtrip(tmp_path):
    p = tmp_path / "cache.json"
    params = CandidateConfig(megastep_depth=4, wave_size=128).as_params()
    rec = TuningCache(p).put("jnp", "cpu", 100, params,
                             measured={"qps": 12.5})
    assert rec["name"] == "jnp/cpu/v128"          # |V| quantized up

    fresh = TuningCache(p)                        # re-read from disk
    hit = fresh.lookup("jnp", "cpu", 100)
    assert hit is not None and hit["params"] == params
    assert hit["measured"]["qps"] == 12.5
    assert quantize_vertices(100) == 128
    assert fresh.lookup("jnp", "cpu", 4000) is None      # other bucket
    assert fresh.lookup("pallas", "cpu", 100) is None    # other backend
    assert cache_key("jnp", "cpu", 100) == "jnp/cpu/v128"


def test_cache_schema_hash_invalidates_stale_records(tmp_path):
    p = tmp_path / "cache.json"
    TuningCache(p).put("jnp", "cpu", 128, CandidateConfig().as_params())
    data = json.loads(p.read_text())
    data["records"]["jnp/cpu/v128"]["schema_hash"] = "deadbeef0000"
    p.write_text(json.dumps(data))
    # the record parses fine but was tuned under a different knob
    # schema: the lookup must miss, not resolve moved-meaning knobs
    assert TuningCache(p).lookup("jnp", "cpu", 128) is None
    assert len(schema_hash()) == 12


def test_cache_resets_on_version_or_shape_mismatch(tmp_path):
    p = tmp_path / "cache.json"
    p.write_text(json.dumps({"version": 99, "records": {"x": {}}}))
    assert TuningCache(p).records() == {}
    p.write_text("not json at all")
    assert TuningCache(p).records() == {}


# ----------------------------------------------------------- resolution
def _seed_cache(monkeypatch, tmp_path, n_vertices=512, backend="jnp",
                **param_overrides):
    """Point the default cache at a tmp file holding one record for
    (backend, this process's device kind, n_vertices)."""
    p = tmp_path / "TUNING_CACHE.json"
    monkeypatch.setenv("REPRO_TUNING_CACHE", str(p))
    params = CandidateConfig(**param_overrides).as_params()
    TuningCache(p).put(backend, device_kind(), n_vertices, params)
    return params


def test_pallas_interpret_refused_off_cpu(monkeypatch):
    """Interpret mode runs kernels in the host interpreter: resolving it
    on an accelerator is an error, not a silent slow path."""
    import jax
    assert kconfig.resolve("pallas_interpret") == "pallas_interpret"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="pallas_interpret"):
        kconfig.resolve("pallas_interpret")
    with kconfig.backend_scope("pallas_interpret"), \
            pytest.raises(ValueError, match="use 'pallas'"):
        kconfig.resolve(None)
    assert kconfig.resolve("pallas") == "pallas"
    assert kconfig.resolve("jnp") == "jnp"


def test_device_kind_never_guesses(monkeypatch):
    """A device JAX cannot read raises instead of keying tuning records
    under a made-up kind."""
    import jax

    def unreadable():
        raise RuntimeError("no backend")

    assert device_kind() == "cpu"
    monkeypatch.setattr(jax, "devices", unreadable)
    with pytest.raises(RuntimeError, match="no backend"):
        device_kind()


def test_resolution_cache_fills_only_unset_knobs(monkeypatch, tmp_path):
    params = _seed_cache(monkeypatch, tmp_path, megastep_depth=4,
                         wave_size=128, block_f=16)
    knobs, rec = resolve_engine_options(MatchOptions(), backend="jnp",
                                        n_vertices=512)
    assert rec["source"] == "tuning-cache"
    assert rec["record"] == cache_key("jnp", device_kind(), 512)
    assert knobs["megastep_depth"] == 4
    assert knobs["wave_size"] == 128
    assert knobs["block_f"] == 16
    assert set(rec["filled_from_cache"]) >= {"megastep_depth",
                                             "wave_size", "block_f"}
    assert rec["params"] == knobs
    del params


def test_resolution_explicit_options_beat_cache(monkeypatch, tmp_path):
    _seed_cache(monkeypatch, tmp_path, megastep_depth=4, wave_size=128)
    opts = MatchOptions(megastep_depth=12, wave_size=256)
    knobs, rec = resolve_engine_options(opts, backend="jnp",
                                        n_vertices=512)
    assert rec["source"] == "tuning-cache"       # record still consulted
    assert knobs["megastep_depth"] == 12         # ...but the user wins
    assert knobs["wave_size"] == 256
    assert "megastep_depth" not in rec["filled_from_cache"]
    assert "wave_size" not in rec["filled_from_cache"]


def test_resolution_scope_override_beats_cache(monkeypatch, tmp_path):
    _seed_cache(monkeypatch, tmp_path, block_f=16)
    with kconfig.kernel_param_scope(block_f=24):
        knobs, _ = resolve_engine_options(MatchOptions(), backend="jnp",
                                          n_vertices=512)
    assert knobs["block_f"] == 24
    assert kconfig.kernel_override("block_f") is None    # scope restored


def test_resolution_builtin_on_miss_or_disable(monkeypatch, tmp_path):
    _seed_cache(monkeypatch, tmp_path, megastep_depth=4, n_vertices=512)
    # different shape bucket: deterministic built-ins
    knobs, rec = resolve_engine_options(MatchOptions(), backend="jnp",
                                        n_vertices=33)
    assert rec["source"] == "builtin" and rec["record"] is None
    assert knobs["megastep_depth"] == \
        ENGINE_TUNABLE_DEFAULTS["megastep_depth"]
    assert knobs["block_f"] == kconfig.DEFAULT_BLOCK_F
    # kill switch: the record exists for this key but is skipped
    monkeypatch.setenv("REPRO_TUNING_DISABLE", "1")
    knobs, rec = resolve_engine_options(MatchOptions(), backend="jnp",
                                        n_vertices=512)
    assert rec["source"] == "builtin"
    assert knobs == {**{k: int(v) for k, v
                        in ENGINE_TUNABLE_DEFAULTS.items()},
                     "block_f": kconfig.DEFAULT_BLOCK_F}


def test_scheduler_consumes_and_surfaces_tuned_record(monkeypatch,
                                                      tmp_path):
    """WaveScheduler construction resolves through the cache and the
    consumed record is visible in scheduler_stats() — the 'tuned record
    visibly consumed' acceptance criterion at unit scale."""
    data = er_labeled_graph(40, 120, 3, seed=6)          # bucket v64
    _seed_cache(monkeypatch, tmp_path, n_vertices=data.n,
                megastep_depth=2, wave_size=32, n_slots=2,
                stack_capacity=256, pattern_capacity=64,
                store_flush_min=8)
    sched = WaveScheduler(data, options=MatchOptions(limit=None))
    assert sched.megastep_depth == 2
    assert sched.wave_size == 32 and sched.n_slots == 2
    assert sched.pattern_capacity == 64
    stats = sched.scheduler_stats()
    assert stats["tuning"]["source"] == "tuning-cache"
    assert stats["tuning"]["record"] == \
        cache_key("jnp", device_kind(), data.n)
    # ...and the tuned schedule still enumerates the oracle set
    q = random_walk_query(data, 4, seed=1)
    qid = sched.submit(q)
    finished = sched.run()
    assert embset(finished[qid].embeddings) == \
        embset(backtrack_deadend(q, data, limit=None).embeddings)


# ------------------------------------------------- weird-config oracle
@pytest.mark.parametrize("case", ["uniform", "trap", "corridor"])
def test_weird_config_matches_oracle(case, monkeypatch):
    """A deliberately awkward tuned point — odd refine block height on
    the interpreted Pallas kernel, shallow megastep, K=3, a pattern
    store squeezed to 16 slots (heavy eviction) — must move time only,
    never results."""
    monkeypatch.setenv("REPRO_TUNING_DISABLE", "1")
    if case == "uniform":
        data = er_labeled_graph(30, 80, 3, seed=2)
        query = random_walk_query(data, 4, seed=3)
    elif case == "trap":
        query, data = trap_graph(n_b=20, n_c=20, n_good=2, tail_len=2,
                                 seed=0)
    else:
        query, data = corridor_graph(n_bait=12, n_spines=2)
    opts = MatchOptions(limit=None, kpr=3, megastep_depth=3,
                        pattern_capacity=16, stack_capacity=256,
                        wave_size=32, n_slots=2, store_flush_min=1)
    with kconfig.backend_scope("pallas_interpret"), \
            kconfig.kernel_param_scope(block_f=5):
        sched = WaveScheduler(data, options=opts)
        assert sched._block_f == 5
        qid = sched.submit(query)
        finished = sched.run()
    want = backtrack_deadend(query, data, limit=None)
    assert embset(finished[qid].embeddings) == embset(want.embeddings)
