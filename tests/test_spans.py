"""The engine's tracing (core/spans.py): host spans that feed the
scheduler's time accumulators, their nesting and arguments in a
profiler trace of a served run, the queue-time and candidate-preparation
counters of ``/metrics``, and the megastep's phase scopes in its HLO."""
import glob
import re
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import spans, vectorized
from repro.core.spans import Acc, span
from repro.core.vectorized import WaveScheduler
from repro.data.graph_gen import (ba_labeled_graph, er_labeled_graph,
                                  query_set)

MEGA_SCOPES = [getattr(spans, k) for k in dir(spans)
               if k.startswith("MEGA_")]


# ----------------------------------------------------------------------
# the helper
# ----------------------------------------------------------------------
def test_span_feeds_its_accumulators_less_the_nested_ones():
    inner = Acc()
    whole, outer = Acc(), Acc(inner)
    with span(spans.SCHED_DIGEST, (whole, outer)) as a:
        time.sleep(0.005)
        with span(spans.STORE_FLUSH, inner, query_id=3) as b:
            time.sleep(0.005)
    assert (b.name, b.ids) == (spans.STORE_FLUSH, {"query_id": 3})
    assert a.t0 < b.t0 < b.t1 < a.t1
    assert inner.s == b.t1 - b.t0
    assert whole.s == a.t1 - a.t0
    assert outer.s == pytest.approx((a.t1 - a.t0) - (b.t1 - b.t0),
                                    abs=1e-12)


def test_span_closes_its_annotation_on_an_exception():
    acc = Acc()
    with pytest.raises(KeyError):
        with span(spans.SCHED_FINISH, acc, query_id=1):
            raise KeyError("x")
    assert acc.s > 0.0


# ----------------------------------------------------------------------
# scheduler_stats keeps its arithmetic
# ----------------------------------------------------------------------
def _inside(rec, outer, names):
    """Recorded spans named in ``names`` that lie inside ``outer``."""
    return [r for r in rec if r[0] in names and r is not outer
            and outer[1] <= r[1] and r[2] <= outer[2]]


def _parent(rec, k):
    """The innermost recorded span that holds span ``k``."""
    r = rec[k]
    holders = [o for j, o in enumerate(rec) if j != k
               and o[1] <= r[1] and r[2] <= o[2]]
    return min(holders, key=lambda o: o[2] - o[1], default=None)


def _old_arithmetic(rec) -> dict:
    """The scheduler's timers as the paired ``perf_counter`` reads used
    to compute them, over the same code regions the spans now cover."""
    def dur(r):
        return r[2] - r[1]

    def total(*names):
        return sum(dur(r) for r in rec if r[0] in names)

    def retire_of(f):          # _finish, less the flushes inside it
        return dur(f) - sum(dur(x) for x in
                            _inside(rec, f, {spans.STORE_FLUSH}))

    def digest_of(d):          # the fold, less retirements and flushes
        return max(0.0, dur(d)
                   - sum(retire_of(f) for f in
                         _inside(rec, d, {spans.SCHED_FINISH}))
                   - sum(dur(x) for x in
                         _inside(rec, d, {spans.STORE_FLUSH})))

    retire = {spans.SCHED_RETIRE_DEVICE, spans.SCHED_RETIRE_WAVE}
    # the timed digest reads sit directly in a retire; the reads the
    # fold makes itself were host time before and still are
    sync = sum(dur(r) for k, r in enumerate(rec)
               if r[0] == spans.SCHED_READBACK
               and (_parent(rec, k) or ("",))[0] in retire)
    return {
        "host_admission_time_s": total(spans.SCHED_ADMIT),
        "dispatch_time_s": total(spans.SCHED_DISPATCH_DEVICE,
                                 spans.SCHED_DISPATCH_WAVE),
        "device_sync_time_s": sync,
        "host_time_s": total(spans.SCHED_DIGEST),
        "host_flush_time_s": total(spans.STORE_FLUSH),
        "host_retirement_time_s": sum(retire_of(f) for f in rec
                                      if f[0] == spans.SCHED_FINISH),
        "host_digest_time_s": sum(digest_of(d) for d in rec
                                  if d[0] == spans.SCHED_DIGEST),
        "host_prepare_time_s": total(spans.SCHED_PREPARE),
        "prepared": sum(1 for r in rec if r[0] == spans.SCHED_PREPARE),
    }


@pytest.mark.parametrize("schedule", [
    dict(),                                        # device stacks
    dict(device_stacks=False),                     # host megastep waves
    dict(megastep_depth=1)])                       # single-step reference
def test_scheduler_stats_are_the_sums_of_their_spans(schedule,
                                                     monkeypatch):
    """Every timer key of ``scheduler_stats()`` equals the arithmetic
    the scheduler used before its timers became spans, recomputed from
    the spans' own intervals; ``host_ms_per_wave``'s inputs
    (``dispatch_time_s`` + ``host_time_s``) among them."""
    rec = []

    class Recorded(span):
        def __exit__(self, *exc):
            out = super().__exit__(*exc)
            rec.append((self.name, self.t0, self.t1, self.ids))
            return out

    monkeypatch.setattr(spans, "span", Recorded)
    monkeypatch.setattr(vectorized, "span", Recorded)
    data = er_labeled_graph(60, 240, 3, seed=4)
    sch = WaveScheduler(data, n_slots=4, wave_size=32, kpr=8,
                        pattern_cache=True, **schedule)
    for q in query_set(data, 5, 10, seed=8):
        sch.submit(q, limit=None)
    sch.run()
    monkeypatch.undo()
    stats = sch.scheduler_stats()
    want = _old_arithmetic(list(rec))
    assert want["prepared"] == 10 and want["host_time_s"] > 0
    for k, v in want.items():
        assert stats[k] == pytest.approx(v, rel=1e-9, abs=1e-12), k
    names = {r[0] for r in rec}
    assert {spans.SCHED_STEP, spans.SCHED_ADMIT, spans.SCHED_READBACK,
            spans.SCHED_DIGEST, spans.SCHED_FINISH} <= names


# ----------------------------------------------------------------------
# a served run under the profiler
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """A small server answers four queries, then two more rounds of them
    while the profiler records; returns the engine thread's events
    (name, start, end, args) and ``/metrics`` before and after the
    traced rounds."""
    import jax
    from jax.profiler import ProfileData
    from repro.server import MatchServer, ServerArgs
    from repro.server.client import ServeClient
    data = ba_labeled_graph(96, 3, 3, extra_edges=96, seed=3)
    srv = MatchServer(data, ServerArgs(port=0, n_slots=4, wave_size=32,
                                       kpr=8, warmup_queries=0,
                                       metrics_refresh_s=0.05))
    srv.start()
    cli = ServeClient(srv.host, srv.port, timeout=120)
    queries = query_set(data, 4, 4, seed=21)
    out = tmp_path_factory.mktemp("trace")
    try:
        # a first round compiles; the traced rounds then run warm
        for q in queries:
            cli.match(q, options={"limit": None})
        time.sleep(0.2)
        before = cli.metrics()
        jax.profiler.start_trace(str(out))
        for _ in range(2):
            threads = [threading.Thread(
                target=cli.match, args=(q,),
                kwargs={"options": {"limit": None}}) for q in queries]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        time.sleep(0.3)           # a report refresh after the last answer
        jax.profiler.stop_trace()
        after = cli.metrics()
    finally:
        srv.shutdown(drain=False)
        srv.httpd.server_close()
    path = sorted(glob.glob(str(out / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    lines = []
    with warnings.catch_warnings():    # the profiler's stats type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        for pl in ProfileData.from_file(path).planes:
            if not pl.name.startswith("/host:"):
                continue
            for ln in pl.lines:
                ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in ln.events]
                if any(n == spans.SCHED_STEP for n, *_ in ev):
                    lines.append(ev)
    assert len(lines) == 1, "one host line holds the sched.step spans"
    return lines[0], before, after


def _held_by(ev, outer_names, events):
    """Events named in ``outer_names`` that hold ``ev``."""
    _, s, e, _ = ev
    return [o for o in events if o[0] in outer_names
            and o[1] <= s and e <= o[2] and o is not ev]


def test_engine_spans_nest_and_carry_query_ids(traced_run):
    events, _, _ = traced_run
    names = {n for n, *_ in events}
    assert {spans.SERVER_ADMIT_READY, spans.SERVER_DELIVER,
            spans.SERVER_REPORT, spans.SERVER_WAIT, spans.METRICS_READBACK,
            spans.SCHED_STEP, spans.SCHED_SUBMIT, spans.SCHED_PREPARE,
            spans.SCHED_ADMIT, spans.SCHED_DISPATCH_DEVICE,
            spans.SCHED_RETIRE_DEVICE, spans.SCHED_READBACK,
            spans.SCHED_DIGEST, spans.SCHED_FINISH} <= names
    submits = [e for e in events if e[0] == spans.SCHED_SUBMIT]
    assert len(submits) == 8
    for ev in events:
        n = ev[0]
        if n == spans.SCHED_SUBMIT:
            assert _held_by(ev, {spans.SERVER_ADMIT_READY}, events)
        elif n == spans.SCHED_PREPARE:
            outer = _held_by(ev, {spans.SCHED_SUBMIT}, events)
            assert len(outer) == 1
            assert str(outer[0][3]["query_id"]) == str(ev[3]["query_id"])
        elif n in (spans.SCHED_ADMIT, spans.SCHED_DISPATCH_DEVICE):
            assert _held_by(ev, {spans.SCHED_STEP}, events)
        elif n == spans.SCHED_DIGEST:
            assert _held_by(ev, {spans.SCHED_RETIRE_DEVICE,
                                spans.SCHED_RETIRE_WAVE}, events)
        elif n == spans.SCHED_FINISH:
            assert "query_id" in ev[3]
        elif n == spans.METRICS_READBACK:
            assert _held_by(ev, {spans.SERVER_REPORT}, events)
    ids = {str(e[3]["query_id"]) for e in submits}
    finished = {str(e[3]["query_id"]) for e in events
                if e[0] == spans.SCHED_FINISH}
    assert ids <= finished


def test_metrics_count_queue_time_and_candidate_preparation(traced_run):
    _, before, after = traced_run
    b, a = before["engine"], after["engine"]
    assert a["prepared"] - b["prepared"] == 8
    assert a["queue_waits"] - b["queue_waits"] == 8
    assert a["host_prepare_time_s"] > b["host_prepare_time_s"]
    assert a["queue_wait_s"] > b["queue_wait_s"]
    # four slots, four clients: a request waits for the engine thread's
    # next admission, not for a slot
    assert (a["queue_wait_s"] - b["queue_wait_s"]) / 8 < 5.0


# ----------------------------------------------------------------------
# device scopes
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("hier", [False, True])
def test_megastep_hlo_names_every_phase(hier, backend):
    """Every ``mega.*`` scope reaches the ``op_name`` metadata of the
    lowered device megastep, whichever refine path and backend."""
    from repro.core.engine_step import run_device_megastep
    data = er_labeled_graph(40, 120, 3, seed=6)
    s = WaveScheduler(data, n_slots=4, wave_size=32, kpr=8,
                      hier_adjacency=hier)
    f = 2 * s.wave_size
    low = run_device_megastep.lower(
        s.g, s.qb, s.tb, s.sb, np.full(f, -1, np.int32),
        np.zeros(f, np.int32), np.zeros(f, np.int32), np.zeros(f, bool),
        np.zeros(s.n_slots, bool), np.int32(0), True, np.int32(2),
        kpr=s._mega_kpr, emb_cap=s._emb_cap, backend=backend,
        wave=s.wave_size, block_f=s._block_f, dma_depth=s._dma_depth)
    names = re.findall(r'op_name="([^"]*)"',
                       low.as_text(dialect="hlo", debug_info=True))
    found = {sc for n in names for sc in re.findall(r"mega\.[a-z]+", n)}
    assert found == set(MEGA_SCOPES)
    assert len(MEGA_SCOPES) == 10
