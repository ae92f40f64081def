"""The split of a profiler trace by the program's own names
(``bench/trace_spans.py``) and the readers of the per-layer metrics the
engine's spans feed, on the CPU. No test here touches a chip."""
from __future__ import annotations

import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import run, trace_spans                        # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
MS = 1_000_000
MEGA = "jit(run_device_megastep)/mega.select/while/body/"


def op(name, start_ms, dur_ms, tf_op=None):
    ev = [name, int(start_ms * MS), int(dur_ms * MS)]
    return ev + [{"tf_op": tf_op}] if tf_op else ev


def host(name, start_ms, dur_ms, **args):
    ev = [name, int(start_ms * MS), int(dur_ms * MS)]
    return ev + [args] if args else ev


def synthetic_trace() -> dict:
    """Two megastep executions; the first one's loop encloses a refine
    operation with a nested refine operation, a probe and a select
    operation; the engine line holds server and scheduler spans and a
    Python tracer event, and a second line one stray ``sched.step``."""
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                op("jit_run_device_megastep(7)", 10, 30),
                op("jit_run_device_megastep(7)", 60, 20)]},
            {"name": "XLA Ops", "events": [
                op("%while.3 = while(...)", 10, 30),
                op("%fusion.1 = fusion(...)", 12, 8,
                   MEGA + "mega.refine/and:"),
                op("%fusion.2 = fusion(...)", 14, 2,
                   MEGA + "mega.refine/gather:"),
                op("%fusion.3 = fusion(...)", 20, 5, MEGA + "mega.probe/eq:"),
                op("%fusion.4 = fusion(...)", 26, 2,
                   "jit(run_device_megastep)/mega.select/while:"),
                op("%fusion.5 = fusion(...)", 30, 8,
                   "jit(run_device_megastep)/mega.drain/while/body/add:"),
                op("%fusion.6 = fusion(...)", 60, 20,
                   MEGA + "mega.refine/and:")]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "", "events": [
                host("$server.py:300 _engine_loop", 0, 100),
                host("server.admit_ready", 0, 9),
                host("sched.submit", 1, 7, query_id=1),
                host("sched.prepare", 2, 5, query_id=1),
                host("sched.step", 9, 41),
                host("sched.dispatch_device", 9, 1),
                host("sched.retire_device", 41, 9),
                host("sched.readback", 41, 3),
                host("sched.digest", 44, 6),
                host("server.wait", 52, 5),
                host("sched.step", 57, 5)]},
            {"name": "", "events": [host("sched.step", 85, 10)]},
            {"name": "python", "events": [
                host("start_trace", -2, 2), host("stop_trace", 100, 1)]}]},
    ]}


def test_scopes_are_device_self_time_under_nesting():
    r = trace_spans.reduce_spans(synthetic_trace())
    # fusion.1 loses its nested fusion.2 (same scope); the loop's own
    # time carries no scope; the select op sits in the loop, outside
    # every inner scope
    assert r["scopes"] == {"mega.drain": pytest.approx(0.008),
                           "mega.probe": pytest.approx(0.005),
                           "mega.refine": pytest.approx(0.028),
                           "mega.select": pytest.approx(0.002)}
    assert r["megastep_count"] == 2
    assert r["megastep_device_s"] == pytest.approx(0.050)
    assert trace_spans.self_times([[None, 0, 10], [None, 2, 3],
                                   [None, 3, 1], [None, 6, 2]]) == [5, 2, 1, 2]


def test_idle_goes_to_the_innermost_engine_span():
    r = trace_spans.reduce_spans(synthetic_trace())
    assert r["window_s"] == pytest.approx(0.100)
    want = {"server.admit_ready": 2, "sched.submit": 2, "sched.prepare": 5,
            "sched.dispatch_device": 1, "sched.step": 4,
            "sched.readback": 3, "sched.digest": 6, "server.wait": 5,
            # [50, 52) between spans, and [80, 100): the stray line's
            # sched.step does not count
            "unattributed": 22}
    assert r["idle_by_span"] == {k: pytest.approx(v / 1e3)
                                 for k, v in want.items()}
    gaps = [(g[0], g[2]) for g in r["idle_gaps"]]
    # longest first, a tie in time order
    assert gaps == [("sched.digest", pytest.approx(0.020)),
                    ("unattributed", pytest.approx(0.020)),
                    ("sched.prepare", pytest.approx(0.010))]
    s = trace_spans.summary(r)
    assert s["idle_admission_share"] == pytest.approx(7.0)
    assert s["idle_readback_share"] == pytest.approx(3.0)
    assert s["idle_attributed"] == pytest.approx(1 - 22 / 50)
    assert s["scope_ms_per_megastep"]["mega.refine"] == pytest.approx(14.0)
    assert s["scope_cover"] == pytest.approx(43 / 50)


def test_a_trace_without_spans_or_scopes_splits_into_nothing():
    t = synthetic_trace()
    for p in t["planes"]:
        for ln in p["lines"]:
            ln["events"] = [e[:3] for e in ln["events"]
                            if e[0] != "sched.step"]
    r = trace_spans.reduce_spans(t)
    assert r["scopes"] is None and r["idle_by_span"] is None
    assert r["idle_gaps"][0][0] == trace_spans.UNATTRIBUTED
    assert trace_spans.summary(r) == {}
    t["planes"] = [p for p in t["planes"] if p["name"].startswith("/host")]
    assert trace_spans.reduce_spans(t) is None


def test_a_recorded_tpu_trace_carries_scopes_and_span_arguments():
    """A trace recorded on a TPU v5e by ``fixtures/probe.py``: the
    scopes come from the operations' metadata in the file, which the
    profiler's Python API does not show; the expected self times were
    worked out by painting the operations' intervals, innermost last."""
    t = trace_spans.load_xplane(FIXTURES / "v5e_scoped_probe.xplane.pb")
    dev = next(p for p in t["planes"] if p["name"] == "/device:TPU:0")
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")
    assert len(ops["events"]) == 81
    assert sum(1 for e in ops["events"] if len(e) == 4) == 78
    assert trace_spans.scope_of(ops["events"][0]) == "mega.roots"
    submits = [e for p in t["planes"] for ln in p["lines"]
               for e in ln["events"] if e[0] == "sched.submit"]
    assert sorted(str(e[3]["query_id"]) for e in submits) == ["0", "1", "2"]
    r = trace_spans.reduce_spans(t)
    assert r["scopes"] == {"mega.drain": pytest.approx(2.006e-06),
                           "mega.probe": pytest.approx(1.0608e-05),
                           "mega.refine": pytest.approx(1.3517e-05),
                           "mega.roots": pytest.approx(6.108e-06),
                           "mega.select": pytest.approx(8.311e-06)}
    assert set(r["idle_by_span"]) == {"sched.submit", "sched.readback",
                                      "sched.step", "unattributed"}


def test_a_recorded_chip_slice_splits_as_painted():
    """A 0.2 s slice of a trace recorded on a TPU v5e in the traced run
    of ``human.closed8``: the device's operations with their ``tf_op``
    and the engine thread's program spans, each clipped to the slice.
    The expected numbers were worked out from the slice by painting
    every interval on the slice's event boundaries, the longest first,
    so that the innermost event owns each piece."""
    with gzip.open(FIXTURES / "v5e_spans_slice.json.gz", "rt") as f:
        fx = json.load(f)
    r = trace_spans.reduce_spans(fx["trace"])
    want = fx["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["scopes"] == {k: pytest.approx(v, rel=1e-9)
                           for k, v in want["scopes"].items()}
    assert len(r["scopes"]) == 10
    assert r["idle_by_span"] == {k: pytest.approx(v, rel=1e-9)
                                 for k, v in want["idle_by_span"].items()}
    # every phase inside the megastep's executions, none above them
    assert sum(r["scopes"].values()) <= r["megastep_device_s"]


# ----------------------------------------------------------------------
# readers
# ----------------------------------------------------------------------
def _ctx(before: dict, after: dict) -> dict:
    return {"before": before, "after": after}


@pytest.mark.parametrize("name,keys", [
    ("queue_wait_ms", ("queue_wait_s", "queue_waits")),
    ("prepare_ms_per_query", ("host_prepare_time_s", "prepared"))])
def test_span_counter_readers(name, keys):
    read = run.metric_reader(name).read
    t, n = keys
    assert read(_ctx({t: 1.0, n: 10}, {t: 1.5, n: 30})) == pytest.approx(25.0)
    # no request in the window, or a program that has no such counter
    assert read(_ctx({t: 1.0, n: 10}, {t: 1.0, n: 10})) is None
    assert read(_ctx({"waves": 3}, {"waves": 9})) is None
    assert read(_ctx({}, {t: 1.0, n: 10})) is None
