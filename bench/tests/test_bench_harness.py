"""The benchmark's harness, on the CPU at tiny sizes: answer checks,
trace reduction, lookup by name, generators, the control and planted
faults. No test here touches a chip."""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import checks, reference, run, trace_reduce  # noqa: E402
from bench.graph import LabeledGraph                     # noqa: E402
from bench.queries import query_set                      # noqa: E402

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
TINY = {"vertices": 300, "edges": 1500, "edge_target": 1500, "labels": 6,
        "attach": 3}


def tiny_graph(seed: int = 0) -> LabeledGraph:
    return run.build_data(dict(run.load_json(ROOT / "bench/configs/human.json"),
                               graph_seed=seed, **TINY))


# ----------------------------------------------------------------------
# answer checks
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def answered():
    """A query with a full answer under the limit, its reference answer
    and the indexes the checks use."""
    data = tiny_graph()
    didx, eidx = reference.DataIndex(data), checks.EdgeIndex(data)
    for q in query_set(data, 5, 50, seed=3):
        ref = reference.match(q, didx, 1000)
        if 3 <= len(ref) < 1000:
            return data, q, ref, eidx
    raise AssertionError("no query with 3..999 embeddings")


def test_exact_answer_passes(answered):
    data, q, ref, eidx = answered
    rows = np.asarray(ref[::-1])
    assert checks.check_answer(q, eidx, rows, "ok", ref, 1000) is None


@pytest.mark.parametrize("fault", ["wrong", "duplicated", "missing"])
def test_answer_checks_catch(answered, fault):
    data, q, ref, eidx = answered
    rows = np.asarray(ref)
    if fault == "wrong":
        rows = rows.copy()
        rows[0, 0] = (rows[0, 0] + 1) % data.n
    elif fault == "duplicated":
        rows = np.concatenate([rows, rows[:1]])
    else:
        rows = rows[1:]
    assert checks.check_answer(q, eidx, rows, "ok", ref, 1000) is not None


def test_limit_answers_are_checked_by_count_and_validity(answered):
    data, q, ref, eidx = answered
    rows = np.asarray(ref)
    lim = len(ref)
    assert checks.check_answer(q, eidx, rows, "limit", ref, lim) is None
    assert checks.check_answer(q, eidx, rows[1:], "limit", ref, lim)
    # an "ok" answer that reaches the limit is not exhaustive
    assert checks.check_answer(q, eidx, rows, "ok", ref, lim)


def test_reference_finds_injective_embeddings_only(answered):
    data, q, ref, eidx = answered
    assert len(set(ref)) == len(ref)
    assert checks.invalid_rows(q, eidx, np.asarray(ref)) is None
    loose = reference.match(q, reference.DataIndex(data), None,
                            injective=False)
    assert set(ref) <= set(loose)


# ----------------------------------------------------------------------
# trace reduction
# ----------------------------------------------------------------------
def synthetic_trace() -> dict:
    ms = 1_000_000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_run_device_megastep(7)", 10 * ms, 30 * ms],
                ["jit_run_device_megastep(7)", 60 * ms, 20 * ms],
                ["jit_other(3)", 90 * ms, 5 * ms]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10 * ms, 20 * ms],
                ["fusion.2", 25 * ms, 15 * ms],     # overlaps fusion.1
                ["fusion.1", 60 * ms, 20 * ms],
                ["copy.3", 90 * ms, 5 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "repro-engine", "events": [
                ["digest", 40 * ms, 15 * ms],
                ["admit", 82 * ms, 6 * ms]]},
            {"name": "http", "events": [       # waiting: names no gap
                ["$queue.py:154 get", 30 * ms, 60 * ms]]},
            {"name": "python", "events": [
                ["start_trace", 0, 2 * ms], ["stop_trace", 98 * ms, 2 * ms]]}]},
    ]}


def test_trace_reduce_busy_share_and_program_time():
    r = trace_reduce.reduce_trace(synthetic_trace())
    # from the end of start_trace to the start of stop_trace
    assert r["window_s"] == pytest.approx(0.096)
    # busy: [10, 40) + [60, 80) + [90, 95) = 55 ms
    assert r["busy_s"] == pytest.approx(0.055)
    mega = r["modules"]["jit_run_device_megastep"]
    assert mega == {"count": 2, "device_s": pytest.approx(0.050)}
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.040)]
    # longest gap [40, 60) is named by the host event overlapping it most
    assert r["idle_gaps"][0] == ["digest", pytest.approx(0.020)]
    ctx = {"trace": r}
    assert load_metric("device_idle_share").read(ctx) == pytest.approx(
        100 * (1 - 55 / 96))
    assert load_metric("megastep_device_ms").read(ctx) == pytest.approx(25.0)


def test_trace_without_device_operations_reduces_to_nothing():
    t = synthetic_trace()
    t["planes"] = [p for p in t["planes"] if p["name"].startswith("/host")]
    assert trace_reduce.reduce_trace(t) is None
    assert load_metric("device_idle_share").read({"trace": None}) is None


def test_trace_reduce_on_a_recorded_chip_trace():
    """A slice of a trace recorded on a TPU v5e (a 65,536-vertex power-law
    graph, hier-HBM adjacency, 8 closed-loop clients);
    the expected numbers were worked out from the slice by a separate
    sweep over its event boundaries."""
    with gzip.open(FIXTURES / "v5e_trace_slice.json.gz", "rt") as f:
        fx = json.load(f)
    r = trace_reduce.reduce_trace(fx["trace"])
    want = fx["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    mega = r["modules"]["jit_run_device_megastep"]
    assert mega["count"] == want["megastep_count"]
    assert mega["device_s"] == pytest.approx(want["megastep_device_s"],
                                             rel=1e-9)


# ----------------------------------------------------------------------
# everything BENCHMARK.json names is found by name
# ----------------------------------------------------------------------
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def load_metric(name: str):
    return run.metric_reader(name)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_are_found_by_name(cell):
    spec = run.load_cell(cell)
    config = spec["config"]
    assert (ROOT / "bench" / "graphs" / f"{config['generator']}.py").is_file()
    assert config["name"] == spec["cell"]["config"]
    loop = ROOT / "bench" / "loops" / f"{spec['traffic']['loop']}.py"
    assert loop.is_file()
    assert hasattr(run.load_module(loop, "loop"), "drive")
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert "setup_s" in names
    for name in names:
        assert callable(load_metric(name).read)


def test_scheduler_metrics_are_deltas_over_the_window():
    ctx = {"before": {"waves": 10, "mean_occupancy": 0.5,
                      "dispatch_time_s": 1.0, "host_time_s": 2.0,
                      "deadend_prunes": 5, "rows_created": 40},
           "after": {"waves": 30, "mean_occupancy": 0.25,
                     "dispatch_time_s": 1.1, "host_time_s": 2.3,
                     "deadend_prunes": 15, "rows_created": 80}}
    # (0.25 x 30 - 0.5 x 10) / 20 rows a wave's capacity
    assert load_metric("wave_occupancy").read(ctx) == pytest.approx(12.5)
    assert load_metric("host_ms_per_wave").read(ctx) == pytest.approx(20.0)
    assert load_metric("prune_rate").read(ctx) == pytest.approx(20.0)
    with pytest.raises(FileNotFoundError):
        load_metric("no_such_metric")


@pytest.fixture(scope="module", params=[c["name"] for c in BENCH["configs"]])
def built(request):
    c = {x["name"]: x for x in BENCH["configs"]}[request.param]
    cfg = run.load_json(ROOT / c["file"])
    return c, cfg, run.build_data(cfg)


def test_configs_state_what_they_cut(built):
    """The graph as built, against the published counts: every count
    that differs is listed under ``reduced``, and only those."""
    c, cfg, g = built
    assert cfg["reduced"] == c["reduced"]
    assert cfg["source"] == c["source"]
    have = {"vertices": g.n, "edges": g.n_edges, "labels": g.n_labels}
    assert {k: cfg[k] for k in have} == have
    changed = {k for k, v in cfg["published"].items() if have[k] != v}
    assert changed == set(c["reduced"])


# ----------------------------------------------------------------------
# generators
# ----------------------------------------------------------------------
def test_generators_are_deterministic_and_meet_the_counts(built):
    _, cfg, a = built
    b = run.build_data(cfg)
    assert np.array_equal(a.indices, b.indices)
    assert np.array_equal(a.labels, b.labels)
    assert a.n == cfg["vertices"] and a.n_edges == cfg["edges"]
    assert len(np.unique(a.labels)) == cfg["labels"] == a.n_labels
    # repeated random draws are dropped, so a few fall short
    assert 0.98 * cfg["edge_target"] <= a.n_edges <= cfg["edge_target"]


@pytest.mark.parametrize("generator", sorted(
    p.stem for p in (ROOT / "bench" / "graphs").glob("*.py")))
def test_generators_follow_the_graph_seed(generator):
    cfg = dict(TINY, generator=generator,
               generator_params=["vertices", "edge_target", "labels",
                                 "attach"])
    one = run.build_data(dict(cfg, graph_seed=5))
    assert np.array_equal(one.indices,
                          run.build_data(dict(cfg, graph_seed=5)).indices)
    assert not np.array_equal(
        one.indices, run.build_data(dict(cfg, graph_seed=6)).indices)


def test_the_seed_orders_a_fixed_query_set():
    """The sequence is the traffic's, the same for every seed; the seed
    writes each query's edges in an order of its own, which the program
    reads as the same query."""
    data = tiny_graph()
    traffic = {"query_sizes": [4, 6], "pool": 20, "query_seed": 12}
    p1, w1 = run.build_queries(data, traffic)
    p2, w2 = run.build_queries(data, traffic)
    key = lambda qs: [(q.labels.tolist(), q.edge_list().tolist()) for q in qs]
    assert key(p1) == key(p2) and key(w1) == key(w2)
    assert sorted(q.n for q in p1) == [4] * 10 + [6] * 10
    assert sorted(q.n for q in w1) == [4, 6]

    def bodies(seed):
        rng = np.random.default_rng([seed, 1])
        return [run.wire_body(q, {}, i, rng) for i, q in enumerate(p1)]
    b1, b2, b3 = bodies(2**40 + 3), bodies(2**40 + 3), bodies(2**40 + 4)
    assert b1 == b2 != b3
    from repro.core.graph import Graph
    for q, body in zip(p1, b3):
        w = json.loads(body)["query"]
        got = Graph.from_edges(w["n"], w["edges"], w["labels"], w["n_labels"])
        want = run.program_graph(q)
        assert np.array_equal(got.indptr, want.indptr)
        assert np.array_equal(got.indices, want.indices)
        assert np.array_equal(got.labels, want.labels)


def test_closed_loop_sends_back_to_back_until_the_window_closes():
    """Client ``c`` of ``n`` sends requests ``c``, ``c + n``, ... each as
    soon as its previous one ended, and sends none after ``t_end``."""
    import threading
    import time
    loop = run.load_module(ROOT / "bench" / "loops" / "closed.py", "closed")
    sent, lock = [], threading.Lock()

    def send(i, t_sched):
        with lock:
            sent.append((i, t_sched))
        time.sleep(0.01)

    t0 = time.monotonic() + 0.02
    loop.drive(send, {"clients": 3}, seed=1, t_start=t0, t_end=t0 + 0.2,
               deadline=t0 + 5.0)
    ids = sorted(i for i, _ in sent)
    assert all(t0 <= t <= t0 + 0.2 for _, t in sent)
    for c in range(3):
        mine = [i for i in ids if i % 3 == c]
        assert mine == list(range(c, 3 * len(mine), 3)) and len(mine) >= 5


def test_request_stats_count_the_unfinished_at_the_deadline():
    recs = [
        {"t_sched": 0.0, "t_send": 0.0, "t_first": 0.1, "t_done": 0.3,
         "status": "limit", "size": 8, "chunks": [[0.1, 7], [0.2, 3]]},
        {"t_sched": 1.0, "t_send": 1.2, "t_first": None, "t_done": 1.5,
         "status": "ok", "size": 16, "chunks": []},
        {"t_sched": 2.0, "t_send": 2.0, "t_first": 2.1, "t_done": 9.0,
         "status": "unfinished", "size": 16, "chunks": [[2.1, 50]]}]
    rq = run.request_stats(recs, t_start=0.0, t_end=2.5, deadline=12.0)
    assert rq["latency_ms"] == pytest.approx([300.0, 500.0, 10000.0])
    # no rows: the answer's end; rows but no end: the first rows
    assert rq["ttfe_ms"] == pytest.approx([100.0, 500.0, 100.0])
    assert rq["answered_in_window"] == 2
    # an unanswered request's rows are not counted
    assert rq["embeddings_in_window"] == 10
    rq15 = run.request_stats(recs, t_start=0.15, t_end=2.5, deadline=12.0)
    assert rq15["embeddings_in_window"] == 3
    assert rq["lateness_ms"] == pytest.approx([0.0, 200.0, 0.0])
    # a request due before the window (warm-up traffic) is not in its
    # latencies, but its answer inside the window counts
    rq = run.request_stats(recs, t_start=0.5, t_end=2.5, deadline=12.0)
    assert rq["latency_ms"] == pytest.approx([500.0, 10000.0])
    assert rq["answered_in_window"] == 1


# ----------------------------------------------------------------------
# the command refuses to run without a TPU
# ----------------------------------------------------------------------
def _run(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script), "--workload", "human.closed8",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    out = _run(ROOT / "bench" / "run.py", ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "needs 1 TPU" in out.stderr


def test_run_fails_with_only_the_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run(tmp_path / "bench" / "run.py", tmp_path)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


# ----------------------------------------------------------------------
# the control, and a run with the timed path broken underneath
# ----------------------------------------------------------------------
def test_control_breaking_injectivity_is_not_correct():
    data = tiny_graph()
    pool = query_set(data, 6, 24, seed=5)
    didx = reference.DataIndex(data)
    records = [{"i": i, "status": "ok"} for i in range(len(pool))]
    control = lambda q: reference.match(q, didx, 1000, injective=False)
    res = run.check_run(data, pool, records, {}, 1000, seed=1,
                        answer=control)
    assert res["wrong_answers"] > 0
    exact = lambda q: reference.match(q, didx, 1000)
    res = run.check_run(data, pool, records, {}, 1000, seed=1, answer=exact)
    assert res["wrong_answers"] == 0


def _alter(rows):
    rows = [list(r) for r in rows]
    if rows:
        rows[0][0] += 1
    return rows


def _drop_half(rows):
    return list(rows)[::2]


@pytest.mark.parametrize("fault", [None, "alter", "drop_half"])
def test_run_catches_a_broken_answer_path(monkeypatch, fault):
    """A whole run at a tiny size, on the CPU: sound, then with the rows
    altered or half of each chunk dropped where the server produces
    them. Only the sound run is correct."""
    from repro.server import protocol
    if fault is not None:
        broken = {"alter": _alter, "drop_half": _drop_half}[fault]
        real = protocol.chunk_event
        monkeypatch.setattr(protocol, "chunk_event",
                            lambda qid, seq, rows: real(qid, seq,
                                                        broken(rows)))
    spec = run.load_cell("human.closed8")
    spec["config"] = dict(spec["config"], **TINY)
    spec["traffic"] = dict(spec["traffic"], pool=200, query_sizes=[4, 6],
                           drain_s=60, warmup_s=1.0)
    out = run.run_cell(spec, seed=2**35 + 9, seconds=2.0, trace=False,
                       log=lambda s: None)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault is None)
    rows = [r for r in out["records"] if r["n_rows"]]
    assert rows and all(sum(n for _, n in r["chunks"]) == r["n_rows"]
                        for r in rows)
    if fault is not None:
        assert out["checks"]["wrong_answers"]["value"] > 0
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
