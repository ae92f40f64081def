"""Benchmark of the served subgraph matcher on one TPU chip.

    python3 bench/run.py --workload human.closed8 --seed 7 --seconds 10 --trace 0

One process owns the chip, the server and the profiler. A run:

1. fixes JAX's persistent compilation cache at ``<checkout>/.jax_cache``
   and turns it on through the program's ``enable_compile_cache``;
2. exits 2, printing no result, unless JAX's devices are TPUs, as many
   as the cell asks for;
3. builds the cell's data graph (``bench/configs/<config>.json``, one
   generator in ``bench/graphs/``) and the traffic's fixed query
   sequence; ``--seed`` writes each query's edges in an order of its own
   and draws the answers the reference re-derives;
4. starts the production server in this process,
   ``MatchServer(data, ServerArgs(port=0, ...))``, ``warmup()``,
   ``start()``, with only the configuration's ``server_args`` set;
5. drives ``POST /v1/match`` from a child process that never imports JAX
   (``bench/client.py``), with the traffic loop the cell's traffic file
   names (``bench/traffic/<traffic>.json`` -> ``bench/loops/<loop>.py``);
   warm-up requests over the wire end the set-up;
6. starts the traffic ``warmup_s`` (traffic file) before the window of
   ``--seconds``, reads ``GET /metrics`` as the window opens and as it
   closes; requests in flight at its end drain until a deadline;
7. with ``--trace 1``, traces a few seconds of the window with
   ``jax.profiler`` into the temporary directory and reduces the trace
   (``bench/trace_reduce.py``);
8. checks every answer (``bench/checks.py``) against the reference
   matcher (``bench/reference.py``), after the server has stopped;
9. prints the metrics of ``BENCHMARK.json`` for the cell, each read by
   ``bench/metrics/<name>.py``: the ``end_to_end`` ones with
   ``--trace 0``, the ``per_layer`` ones with ``--trace 1``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its
limit. The same comparisons are the last lines of standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()     # set-up is timed from here

import argparse                  # noqa: E402
import importlib.util            # noqa: E402
import json                      # noqa: E402
import os                        # noqa: E402
import pathlib                   # noqa: E402
import shutil                    # noqa: E402
import subprocess                # noqa: E402
import sys                       # noqa: E402
import tempfile                  # noqa: E402
import threading                 # noqa: E402
import urllib.request            # noqa: E402

import numpy as np               # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import checks, reference                       # noqa: E402
from bench.graph import LabeledGraph                      # noqa: E402
from bench.queries import random_walk_query               # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
ANSWERED = checks.ANSWERED
# the profiler traces this slice of the window: long enough for tens of
# waves on the slowest cell, short enough that the trace stays small
TRACE_START_S = 2.0
TRACE_SECONDS = 4.0
WARMUP_TIMEOUT_S = 300.0
# answers re-derived by the reference matcher: a seeded sample of the
# exhaustive ones (status ok), up to this many; every answer gets the
# checks that need no reference
REFERENCE_SAMPLE = 256


# ----------------------------------------------------------------------
# the cell, from BENCHMARK.json and the files it names
# ----------------------------------------------------------------------
def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: pathlib.Path = ROOT) -> dict:
    """The cell's entry, configuration, traffic and metric entries."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def metric_reader(name: str):
    """The reader of metric ``name``, ``bench/metrics/<name>.py``."""
    return load_module(HERE / "metrics" / f"{name}.py",
                       f"bench_metric_{name}")


def build_data(config: dict) -> LabeledGraph:
    gen = load_module(HERE / "graphs" / f"{config['generator']}.py",
                      f"bench_graph_{config['generator']}")
    return gen.build(int(config["graph_seed"]),
                     **{k: config[k] for k in config["generator_params"]})


def build_queries(data: LabeledGraph, traffic: dict
                  ) -> tuple[list[LabeledGraph], list[LabeledGraph]]:
    """The query sequence a run sends, and one warm-up query per size:
    the traffic's fixed set, drawn from its ``query_seed``, ``pool``
    random-walk queries with each size of ``query_sizes`` equally often,
    like a dataset's fixed query set, in the order drawn. The order is
    the same for every ``--seed``: the pattern store carries what one
    query learned into the next and queries in flight share each wave,
    so another order is other work, and a few queries of the set cost
    tens of seconds, so another order also puts other ones in the
    window."""
    sizes = list(traffic["query_sizes"])
    n = int(traffic["pool"])
    rng = np.random.default_rng(int(traffic["query_seed"]))
    per = np.resize(np.asarray(sizes), n)
    rng.shuffle(per)
    base = int(rng.integers(0, 2**62))
    pool = [random_walk_query(data, int(k), seed=base + i)
            for i, k in enumerate(per)]
    warm = [random_walk_query(data, int(k), seed=base + n + j)
            for j, k in enumerate(sizes)]
    return pool, warm


def wire_body(q: LabeledGraph, options: dict, request_id: int,
              rng: np.random.Generator | None = None) -> str:
    """A ``POST /v1/match`` body, wire version 1. With ``rng`` the edge
    list is written in an order it draws, each edge either way round:
    the same query, put another way."""
    edges = q.edge_list()
    if rng is not None:
        edges = edges[rng.permutation(len(edges))]
        flip = rng.random(len(edges)) < 0.5
        edges[flip] = edges[flip][:, ::-1]
    return json.dumps({
        "v": 1, "tenant": "default", "options": dict(options),
        "request_id": request_id,
        "query": {"n": int(q.n), "labels": [int(x) for x in q.labels],
                  "edges": edges.tolist(),
                  "n_labels": int(q.n_labels)}})


def program_graph(g: LabeledGraph):
    from repro.core.graph import Graph
    return Graph.from_edges(g.n, g.edge_list().tolist(), g.labels,
                            g.n_labels)


# ----------------------------------------------------------------------
# a run
# ----------------------------------------------------------------------
class CompileCounter:
    """Names the programs JAX compiles or loads from its persistent
    cache, and counts the cache's misses (real compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax
        self.names: list[str] = []
        self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            with self._lock:
                self.names.append((str(kw.get("fun_name")), duration))

    def _on_event(self, event: str, **kw) -> None:
        if event == self.MISS:
            with self._lock:
                self.misses += 1


def get_metrics(host: str, port: int) -> dict:
    with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                timeout=30) as r:
        return json.loads(r.read())


def read_client(proc: subprocess.Popen, timeout: float
                ) -> tuple[list[dict], dict[int, np.ndarray]]:
    """The child's records and the rows of each request."""
    out, _ = proc.communicate(timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"load generator exited {proc.returncode}")
    head, _, blob = out.partition(b"\n")
    records = json.loads(head)["records"]
    rows, off = {}, 0
    for r in records:
        k, w = r["n_rows"], r["row_width"]
        rows[r["i"]] = np.frombuffer(blob, "<i4", k * w, off).reshape(k, w)
        off += 4 * k * w
    return records, rows


def percentile(values, q: float) -> float | None:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def check_run(data: LabeledGraph, pool: list[LabeledGraph],
              records: list[dict], rows: dict, limit: int, seed: int,
              answer=None) -> dict:
    """Check every answered request; the reference matcher re-derives a
    seeded sample of the exhaustive (``ok``) answers. ``answer``, where
    given, replaces the served rows (the control). Returns counts and
    the first reasons."""
    eidx = checks.EdgeIndex(data)
    didx = reference.DataIndex(data)
    answered = [r for r in records if r["status"] in ANSWERED]
    ok = [r["i"] for r in answered if r["status"] == "ok"]
    rng = np.random.default_rng([seed, 2])
    sample = set(rng.permutation(ok)[:REFERENCE_SAMPLE].tolist())
    wrong, why, t0 = 0, [], time.monotonic()
    for r in answered:
        q = pool[r["i"] % len(pool)]
        if answer is None:
            got, status = rows[r["i"]], r["status"]
        else:
            got = np.asarray(answer(q), np.int64).reshape(-1, q.n)
            status = "limit" if len(got) >= limit else "ok"
        ref = (reference.match(q, didx, limit)
               if r["i"] in sample or answer is not None else None)
        reason = checks.check_answer(q, eidx, got, status, ref, limit)
        if reason is not None:
            wrong += 1
            if len(why) < 5:
                why.append(f"request {r['i']} (n={q.n}): {reason}")
    return {"checked": len(answered), "referenced": len(sample),
            "wrong_answers": wrong, "reasons": why,
            "check_s": time.monotonic() - t0}


def request_stats(records: list[dict], t_start: float, t_end: float,
                  deadline: float) -> dict:
    """Per-request times from the client's records, of the requests due
    inside the window; a request that never ended counts in the tails at
    the deadline. The answers and the embeddings that came inside the
    window are counted whatever request they belong to, those sent
    before it included; embeddings only of requests that were answered
    (``ok``/``limit``)."""
    lat, ttfe, by_size = [], [], {}
    window = [r for r in records if r["t_sched"] >= t_start]
    for r in window:
        end = r["t_done"] if r["status"] != "unfinished" else deadline
        lat.append((end - r["t_sched"]) * 1e3)
        first = r["t_first"] if r["t_first"] is not None else end
        ttfe.append((first - r["t_sched"]) * 1e3)
        by_size.setdefault(r["size"], []).append(lat[-1])
    late = [(r["t_send"] - r["t_sched"]) * 1e3 for r in window]
    done_in = [r for r in records if r["status"] in ANSWERED
               and t_start <= r["t_done"] <= t_end]
    rows_in = sum(n for r in records if r["status"] in ANSWERED
                  for t, n in r.get("chunks", ()) if t_start <= t <= t_end)
    return {"latency_ms": lat, "ttfe_ms": ttfe,
            "answered_in_window": len(done_in),
            "embeddings_in_window": rows_in,
            "lateness_ms": late, "latency_ms_by_size": by_size}


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_process: float = T_PROCESS, log=None) -> dict:
    """One run of a cell. Returns the result line's object (without the
    device) and what the earlier lines print. The caller has checked for
    the chip."""
    import jax
    from repro.server import MatchServer, ServerArgs

    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    config, traffic = spec["config"], spec["traffic"]
    compiles = CompileCounter()
    data = build_data(config)
    pool, warm = build_queries(data, traffic)
    options = traffic.get("options", {})
    # the seed writes each query's edges in an order of its own
    wire_rng = np.random.default_rng([seed, 1])
    bodies = [wire_body(q, options, i, wire_rng) for i, q in enumerate(pool)]
    # the server's own warm-up compiles the device-stack path only; a
    # query the scheduler moves to host segments (a wedged device stack)
    # runs programs it has not compiled, so each warm-up query is also
    # sent once with two root segments, which takes that path
    warm_bodies = [wire_body(q, dict(options, **extra), -1 - j)
                   for extra in ({}, {"parallelism": 2})
                   for j, q in enumerate(warm)]
    log(f"data |V|={data.n} |E|={data.n_edges} labels={data.n_labels}; "
        f"pool {len(pool)} queries, sizes {traffic['query_sizes']}")

    args = ServerArgs(port=0, **config["server_args"])
    server = MatchServer(program_graph(data), args)
    child = None
    try:
        server.warmup()
        server.start()
        child = subprocess.Popen(
            [sys.executable, str(HERE / "client.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        child.stdin.write((json.dumps({
            "host": server.host, "port": server.port,
            "loop": traffic["loop"], "params": traffic["params"],
            "seed": seed, "bodies": bodies, "warmup_bodies": warm_bodies,
            "warmup_timeout_s": WARMUP_TIMEOUT_S}) + "\n").encode())
        child.stdin.flush()
        if child.stdout.readline().strip() != b"ready":
            raise RuntimeError("load generator did not get ready")
        # the traffic starts warmup_s before the window: the programs its
        # shapes need are compiled or loaded, and the queue is in its
        # steady state, when the window opens
        t_begin = time.monotonic() + 0.05
        t_start = t_begin + float(traffic["warmup_s"])
        t_end = t_start + seconds
        deadline = t_end + float(traffic["drain_s"])
        child.stdin.write(f"go {t_begin} {t_end} {deadline}\n".encode())
        child.stdin.flush()
        time.sleep(max(0.0, t_start - time.monotonic()))
        before = get_metrics(server.host, server.port)
        n_compiled, n_missed = len(compiles.names), compiles.misses
        setup_s = t_start - t_process
        reduced = None
        if trace:
            trace_dir = pathlib.Path(tempfile.gettempdir()) / "bench-trace"
            shutil.rmtree(trace_dir, ignore_errors=True)
            time.sleep(max(0.0, t_start + TRACE_START_S - time.monotonic()))
            jax.profiler.start_trace(str(trace_dir))
            time.sleep(min(TRACE_SECONDS, seconds))
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t_end - time.monotonic()))
        after = get_metrics(server.host, server.port)
        compiled_in_window = compiles.names[n_compiled:]
        missed_in_window = compiles.misses - n_missed
        records, rows = read_client(
            child, timeout=deadline - time.monotonic() + 60.0)
        child = None
        mem = jax.devices()[0].memory_stats() or {}
        stats_engine = after["engine"]
    finally:
        if child is not None:
            child.kill()
            child.wait()
        server.shutdown(drain=False)
        server.httpd.server_close()      # shutdown() leaves it listening
    if server.error is not None:
        raise RuntimeError(f"engine failed: {server.error!r}")
    if trace:
        from bench import trace_reduce
        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        if files:
            reduced = trace_reduce.reduce_trace(
                trace_reduce.load_xplane(files[-1]))
        log(f"trace: {files[-1] if files else 'no xplane file'}")
        shutil.rmtree(trace_dir, ignore_errors=True)

    for r in records:
        r["size"] = pool[r["i"] % len(pool)].n
    window = [r for r in records if r["t_sched"] >= t_start]
    rq = request_stats(records, t_start, t_end, deadline)
    limit = int(config["server_args"]["limit"])
    chk = check_run(data, pool, records, rows, limit, seed)
    attempted = len(window)
    failed = sum(1 for r in window if r["status"] not in ANSWERED)
    unanswered = sum(1 for r in records
                     if r["status"] in ("unfinished", "client-error"))
    late = rq["lateness_ms"]
    log(f"generator lateness ms: median {percentile(late, 50)} max "
        f"{max(late) if late else None}; pool wraps "
        f"{max((r['i'] for r in records), default=0) // len(pool)}")
    log(f"compiles inside the window: {len(compiled_in_window)} programs "
        f"compiled or loaded ({missed_in_window} persistent-cache misses), "
        f"{sum(d for _, d in compiled_in_window):.3f} s: "
        f"{sorted(set(n for n, _ in compiled_in_window))}")
    moved = {k: v - before["engine"].get("faults", {}).get(k, 0)
             for k, v in stats_engine.get("faults", {}).items()}
    log(f"fault counters moved in the window: "
        f"{ {k: v for k, v in moved.items() if v} }")
    log(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')}")
    log(f"inside the window: {rq['answered_in_window']} answers "
        f"({rq['answered_in_window'] / seconds} /s), "
        f"{rq['embeddings_in_window']} embeddings "
        f"({rq['embeddings_in_window'] / seconds} /s)")
    log(f"latency ms: p50 {percentile(rq['latency_ms'], 50)} p95 "
        f"{percentile(rq['latency_ms'], 95)}; time to first embedding ms "
        f"p95 {percentile(rq['ttfe_ms'], 95)}")
    log("latency ms by query size: " + json.dumps({
        k: {"n": len(v), "p50": percentile(v, 50), "p95": percentile(v, 95)}
        for k, v in sorted(rq["latency_ms_by_size"].items())}))
    log(f"statuses: " + json.dumps({
        s: sum(1 for r in records if r["status"] == s)
        for s in sorted({r["status"] for r in records})}))
    log(f"checked {chk['checked']} answers, {chk['referenced']} against "
        f"the reference, in {chk['check_s']:.1f}s")
    for reason in chk["reasons"]:
        log(f"wrong: {reason}")

    ctx = {"records": records, "window_s": seconds, "setup_s": setup_s,
           "requests": rq, "before": before["engine"],
           "after": stats_engine, "trace": reduced}
    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks_line = {
        "wrong_answers": {"value": chk["wrong_answers"], "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0}}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks_line.values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "memory_peak_bytes": mem.get("peak_bytes_in_use"),
           "trace": reduced, "records": records, "checks": checks_line}
    return out


def enable_cache() -> None:
    """Give the program the cache directory inside the checkout, turn
    the cache on through the program, and keep every program in it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # every program the window runs must come from the cache, however
    # quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    spec = load_cell(a.workload)
    enable_cache()
    import jax
    devices = jax.devices()
    want = int(spec["cell"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: needs {want} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2

    out = run_cell(spec, a.seed, a.seconds, bool(a.trace))
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "device": device}
    if a.trace:
        tr = out["trace"]
        if tr is None:
            print("bench: the trace holds no device operation",
                  file=sys.stderr)
            return 1
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
