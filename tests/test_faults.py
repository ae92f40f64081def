"""Fault-tolerant matching runtime (DESIGN.md §8): deterministic fault
injection, watchdog + retry/backoff, digest validation + quarantine,
host-path fallback, shard-loss recovery, checkpoint validation, typed
timeouts, and overload shedding.

The standing soundness bar for every scenario: an injected fault may
cost work (retries, re-enumeration, host fallback) but never results —
the final embedding set equals the sequential oracle's, and co-resident
queries are bit-identical to a fault-free run.
"""
import pathlib

import numpy as np
import pytest

from repro.api import (MatchError, MatchSession, MatchTimeout,
                       QueueFull)
from repro.core.backtrack import backtrack_deadend
from repro.core.distributed import CheckpointCorrupt, DistributedMatcher
from repro.core.faults import FaultInjected, FaultPlan, FaultSpec
from repro.data.graph_gen import er_labeled_graph, query_set, trap_graph


def embset(embs):
    return set(tuple(np.asarray(e).tolist()) for e in embs)


def sorted_rows(embs):
    return sorted(tuple(np.asarray(e).tolist()) for e in embs)


@pytest.fixture(scope="module")
def workload():
    data = er_labeled_graph(35, 100, 3, seed=11)
    queries = query_set(data, 4, 6, seed=5)
    oracle = [embset(backtrack_deadend(q, data, limit=None).embeddings)
              for q in queries]
    return data, queries, oracle


def run_one(data, q, oracle_set, *, expect_status="ok", **knobs):
    """One query through a fresh engine session; asserts terminal status
    and oracle equality, returns (result, fault counters, session)."""
    s = MatchSession(data, wave_size=64, n_slots=4, **knobs)
    h = s.submit(q, limit=None)
    r = h.result()
    f = s.scheduler.scheduler_stats()["faults"]
    assert r.status == expect_status
    if expect_status == "ok":
        assert embset(r.embeddings) == oracle_set
    return r, f, s


# ----------------------------------------------------------------------
# the fault plan itself
# ----------------------------------------------------------------------
def test_fault_plan_is_deterministic():
    plan = FaultPlan([FaultSpec("dispatch", "exception", at=2, times=2),
                      FaultSpec("flush", "exception", at=1)])
    hits = [plan.poke("dispatch") is not None for _ in range(5)]
    assert hits == [False, True, True, False, False]
    assert plan.poke("flush") is not None
    assert [(s, k, n) for s, k, n, _ in plan.fired] == \
        [("dispatch", "exception", 2), ("dispatch", "exception", 3),
         ("flush", "exception", 1)]
    plan.reset()
    assert plan.peek("dispatch") == 0 and plan.fired == []
    # identical replay after reset: same crossings fire
    assert [plan.poke("dispatch") is not None for _ in range(5)] == hits


def test_fault_spec_validation():
    with pytest.raises(ValueError):
        FaultSpec("nonsense", "exception")
    with pytest.raises(ValueError):
        FaultSpec("dispatch", "shard_loss")     # wrong kind for site
    with pytest.raises(ValueError):
        FaultSpec("dispatch", "exception", at=0)


# ----------------------------------------------------------------------
# tentpole: dispatch retry / watchdog / digest quarantine / fallback
# ----------------------------------------------------------------------
def test_dispatch_exception_is_retried(workload):
    """A failing dispatch re-runs with backoff and the query still
    completes on the device path — no fallback, no lost embeddings."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("dispatch", "exception", at=2)])
    _, f, _ = run_one(data, queries[0], oracle[0], faults=plan)
    assert f["dispatch_retries"] >= 1
    assert f["fallbacks"] == 0 and f["errors"] == 0


def test_retry_exhaustion_demotes_to_host(workload):
    """times > dispatch_retries exhausts the retry budget; the query is
    quarantined and completes on the host fallback, oracle-equal."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("dispatch", "exception", at=2, times=5)])
    r, f, _ = run_one(data, queries[0], oracle[0], faults=plan)
    assert f["dispatch_retries"] == 2          # budget fully spent
    assert f["quarantined"] >= 1 and f["fallbacks"] >= 1
    assert r.stats.fallback


@pytest.mark.parametrize("failure", ["kernel_lowering", "xla_compile"])
def test_compile_failure_stops_the_run(workload, monkeypatch, failure):
    """A device program that fails to compile is not a runtime fault:
    its error propagates out of the first dispatch — never retried,
    quarantined or replayed on the host single-step path, which would
    answer correctly while hiding a device that cannot run the
    program."""
    import jax
    from repro.core import vectorized
    from repro.kernels.config import backend_scope
    data, queries, _ = workload
    calls = []
    if failure == "xla_compile":
        # what XLA raises when the TPU compiler refuses a program
        def refuse(*args, **kwargs):
            calls.append(1)
            raise jax.errors.JaxRuntimeError(
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                "Ran out of memory in memory space vmem.")
        monkeypatch.setattr(vectorized, "run_device_megastep", refuse)
        backend, expected = "jnp", jax.errors.JaxRuntimeError
    else:
        # the compiled Pallas kernel cannot lower for the CPU backend
        backend, expected = "pallas", ValueError
    with backend_scope(backend):
        s = MatchSession(data, wave_size=64, n_slots=4)
        h = s.submit(queries[0], limit=None)
        with pytest.raises(expected):
            h.result()
    f = s.scheduler.scheduler_stats()["faults"]
    assert f["dispatch_retries"] == 0
    assert f["quarantined"] == f["fallbacks"] == f["errors"] == 0
    assert not any(r.host_only for r in s.scheduler.queue)
    assert len(calls) == (failure == "xla_compile")


def test_hang_fires_watchdog_then_fallback(workload):
    """A hung dispatch retires through the watchdog instead of blocking
    the pipeline; the affected query completes via fallback."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("dispatch", "hang", at=2)])
    _, f, _ = run_one(data, queries[0], oracle[0], faults=plan)
    assert f["hangs"] >= 1 and f["fallbacks"] >= 1


def test_digest_corruption_is_caught_never_absorbed(workload):
    """A bit-corrupted digest (broken Lemma-4 conservation + negative
    counter) is rejected by the validator — the slot is quarantined and
    re-run, never silently folded into results."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("digest", "corrupt", at=1)])
    _, f, _ = run_one(data, queries[0], oracle[0], faults=plan)
    assert f["digest_failures"] >= 1
    assert f["quarantined"] >= 1 and f["fallbacks"] >= 1


def test_digest_overflow_is_caught(workload):
    """A forged live count past stack_capacity trips the capacity
    invariant."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("digest", "overflow", at=1)])
    _, f, _ = run_one(data, queries[0], oracle[0], faults=plan)
    assert f["digest_failures"] >= 1


def test_corrupt_digest_only_hits_target_slot(workload):
    """Quarantine blast radius: with the corruption aimed at slot 0,
    the co-resident query's embedding rows are bit-identical to a
    fault-free run's."""
    data, queries, oracle = workload
    qa, qb = queries[0], queries[1]

    def run(plan):
        s = MatchSession(data, wave_size=64, n_slots=4, faults=plan)
        ha = s.submit(qa, limit=None)
        hb = s.submit(qb, limit=None)
        return ha.result(), hb.result(), s

    ra0, rb0, _ = run(None)                        # fault-free baseline
    plan = FaultPlan([FaultSpec("digest", "corrupt", at=1, slot=0)])
    ra1, rb1, s = run(plan)
    assert s.scheduler.scheduler_stats()["faults"]["digest_failures"] >= 1
    assert ra1.status == "ok" and rb1.status == "ok"
    assert embset(ra1.embeddings) == oracle[0]
    assert sorted_rows(rb1.embeddings) == sorted_rows(rb0.embeddings)


def test_error_status_when_fallback_disabled(workload):
    """fallback_on_failure=False: a quarantined query terminates with
    status='error', a typed MatchError on the handle, and done() that
    never lies."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("digest", "corrupt", at=1)])
    s = MatchSession(data, wave_size=64, n_slots=4, faults=plan,
                     fallback_on_failure=False)
    h = s.submit(queries[0], limit=None)
    r = h.result()
    assert r.status == "error" and r.aborted
    assert h.done()
    assert isinstance(h.error, MatchError)
    assert "digest validation failed" in str(h.error)
    assert s.scheduler.scheduler_stats()["faults"]["errors"] == 1


def test_admission_fault_errors_the_request(workload):
    data, queries, _ = workload
    plan = FaultPlan([FaultSpec("admission", "exception", at=1)])
    s = MatchSession(data, wave_size=64, n_slots=4, faults=plan)
    h = s.submit(queries[0], limit=None)
    assert h.result().status == "error"
    assert s.scheduler.scheduler_stats()["faults"][
        "admission_failures"] == 1


def test_flush_fault_drops_patterns_soundly():
    """A dropped Δ flush batch loses pruning power only — enumeration
    still matches the oracle exactly (patterns never add results)."""
    q, data = trap_graph(n_b=12, n_c=12, n_good=2, tail_len=2, seed=0)
    oracle = embset(backtrack_deadend(q, data, limit=None).embeddings)
    plan = FaultPlan([FaultSpec("flush", "exception", at=1)])
    s = MatchSession(data, wave_size=64, n_slots=4, megastep_depth=1,
                     device_stacks=False, faults=plan)
    r = s.submit(q, limit=None).result()
    assert r.status == "ok" and embset(r.embeddings) == oracle
    assert s.scheduler.scheduler_stats()["faults"]["flush_drops"] >= 1


def test_host_megastep_path_faults(workload):
    """The same dispatch boundary covers the host megastep pipeline
    (device_stacks=False): exception → retry, hang → watchdog."""
    data, queries, oracle = workload
    knobs = dict(device_stacks=False, adaptive_prune_threshold=1.0)
    plan = FaultPlan([FaultSpec("dispatch", "exception", at=1)])
    _, f, _ = run_one(data, queries[0], oracle[0], faults=plan, **knobs)
    assert f["dispatch_retries"] >= 1
    plan = FaultPlan([FaultSpec("dispatch", "hang", at=1)])
    _, f, _ = run_one(data, queries[0], oracle[0], faults=plan, **knobs)
    assert f["hangs"] >= 1


def test_fault_hooks_are_inert_when_disabled(workload):
    """No FaultPlan: every counter stays zero and results are exact —
    the hooks exist but never fire (zero-cost in the ab_gate sense)."""
    data, queries, oracle = workload
    _, f, _ = run_one(data, queries[0], oracle[0])
    assert all(v == 0 for v in f.values())


# ----------------------------------------------------------------------
# satellites: typed timeout, shedding, checkpoint validation, shard loss
# ----------------------------------------------------------------------
def test_result_timeout_raises_typed_not_blocks(workload):
    data, queries, oracle = workload
    s = MatchSession(data, wave_size=64, n_slots=4)
    h = s.submit(queries[0], limit=None)
    with pytest.raises(MatchTimeout):
        h.result(timeout=0.0)
    assert not h.done()                 # the query is unharmed, not done
    r = h.result()                      # and still completes normally
    assert r.status == "ok" and embset(r.embeddings) == oracle[0]
    assert h.result(timeout=0.0) is r   # completed: returns immediately


def test_overload_shedding_drops_lowest_priority(workload):
    """shed_policy='shed_lowest': a saturated queue sheds the lowest-
    priority requests with status='shed' instead of growing or raising;
    the served queries' results are untouched."""
    data, queries, oracle = workload
    s = MatchSession(data, wave_size=64, n_slots=1, max_queue=2,
                     shed_policy="shed_lowest")
    handles = [s.submit(q, limit=None, priority=i % 3)
               for i, q in enumerate(queries)]
    results = [h.result() for h in handles]
    statuses = [r.status for r in results]
    assert statuses.count("shed") >= 1
    shed_prio = [i % 3 for i, st in enumerate(statuses) if st == "shed"]
    ok_prio = [i % 3 for i, st in enumerate(statuses) if st == "ok"]
    # every shed request had priority <= every served one
    assert max(shed_prio) <= min(ok_prio)
    for i, r in enumerate(results):
        if r.status == "ok":
            assert embset(r.embeddings) == oracle[i]
    f = s.scheduler.scheduler_stats()["faults"]
    assert f["shed"] == statuses.count("shed")
    # the default policy still raises typed backpressure instead
    s2 = MatchSession(data, wave_size=64, n_slots=1, max_queue=1)
    with pytest.raises(QueueFull):
        for q in queries:
            s2.submit(q, limit=None)


def test_server_tallies_shed_and_errors(workload):
    from repro.serving.query_server import QueryServer
    data, queries, _ = workload
    plan = FaultPlan([FaultSpec("admission", "exception", at=1)])
    srv = QueryServer(data, backend="engine", wave_size=64, n_slots=4,
                      faults=plan, fallback_on_failure=False)
    srv.submit_batch(queries[:2])
    rep = srv.slo_report()
    assert rep["errors"] == 1 and rep["shed"] == 0


def test_checkpoint_corrupt_truncated_archive(tmp_path):
    (tmp_path / "state.npz").write_bytes(b"PK\x03\x04 not a real zip")
    with pytest.raises(CheckpointCorrupt, match="unreadable"):
        DistributedMatcher.load_state(str(tmp_path))


def test_checkpoint_corrupt_names_the_bad_field(tmp_path):
    # missing required field
    np.savez_compressed(tmp_path / "state.npz",
                        version=np.int64(3), n_shards=np.int64(2))
    with pytest.raises(CheckpointCorrupt, match="phi_floor"):
        DistributedMatcher.load_state(str(tmp_path))
    # unsupported version
    np.savez_compressed(
        tmp_path / "state.npz", version=np.int64(99),
        n_shards=np.int64(2), phi_floor=np.int64(1),
        pending_roots=np.zeros(0, np.int32),
        embeddings=np.zeros((0, 0), np.int32))
    with pytest.raises(CheckpointCorrupt, match="version"):
        DistributedMatcher.load_state(str(tmp_path))
    # wrong-shape array
    np.savez_compressed(
        tmp_path / "state.npz", version=np.int64(3),
        n_shards=np.int64(2), phi_floor=np.int64(1),
        pending_roots=np.zeros((2, 2), np.int32),
        embeddings=np.zeros((0, 0), np.int32))
    with pytest.raises(CheckpointCorrupt, match="pending_roots"):
        DistributedMatcher.load_state(str(tmp_path))
    # Δ entry arrays with mismatched lengths
    np.savez_compressed(
        tmp_path / "state.npz", version=np.int64(3),
        n_shards=np.int64(2), phi_floor=np.int64(1),
        pending_roots=np.zeros(0, np.int32),
        embeddings=np.zeros((0, 0), np.int32),
        delta_pos=np.zeros(3, np.int32), delta_v=np.zeros(3, np.int32),
        delta_phi=np.zeros(3, np.int32), delta_mu=np.zeros(3, np.int32),
        delta_mask=np.zeros(2, np.uint64),
        delta_hits=np.zeros(3, np.int64))
    with pytest.raises(CheckpointCorrupt, match="delta_mask"):
        DistributedMatcher.load_state(str(tmp_path))


def test_checkpoint_valid_roundtrip_still_loads(tmp_path, workload):
    """The validation pass accepts everything save_state writes."""
    data, queries, oracle = workload
    m = DistributedMatcher(data, n_shards=2, wave_size=64)
    out = m.match(queries[0], limit=None,
                  checkpoint_dir=str(tmp_path))
    assert embset(out.embeddings) == oracle[0]
    ck = DistributedMatcher.load_state(str(tmp_path))
    assert ck is not None and ck.version == 3
    assert len(ck.pending_roots) == 0


def test_shard_loss_recovers_on_survivors(tmp_path, workload):
    """A shard killed mid-run re-seeds its unresolved roots onto the
    3 survivors from the micro-checkpoints; the final embedding set is
    identical to the fault-free 4-shard run."""
    data, queries, oracle = workload
    ref = DistributedMatcher(data, n_shards=4, wave_size=64).match(
        queries[0], limit=None)
    plan = FaultPlan([FaultSpec("shard", "shard_loss", at=2)])
    m = DistributedMatcher(data, n_shards=4, wave_size=64,
                           micro_checkpoint_every=1, faults=plan)
    out = m.match(queries[0], limit=None, checkpoint_dir=str(tmp_path))
    assert m.n_shards == 3                       # one shard gone
    assert len(plan.fired) == 1
    assert embset(out.embeddings) == embset(ref.embeddings) == oracle[0]


def test_checkpoint_save_fault_keeps_previous_snapshot(tmp_path,
                                                       workload):
    """An injected checkpoint-save failure skips that snapshot; the
    match completes and the run is unharmed."""
    data, queries, oracle = workload
    plan = FaultPlan([FaultSpec("checkpoint", "exception", at=1,
                                times=100)])
    m = DistributedMatcher(data, n_shards=2, wave_size=64,
                           micro_checkpoint_every=1, faults=plan)
    out = m.match(queries[0], limit=None, checkpoint_dir=str(tmp_path))
    assert embset(out.embeddings) == oracle[0]
    assert plan.peek("checkpoint") >= 1
    assert not (tmp_path / "state.npz").exists()   # every save skipped
