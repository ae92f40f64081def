"""Open-loop load benchmark for the network serving tier -> the
``serving_tier`` section of BENCH_serving.json (DESIGN.md §10).

Unlike ``serving_bench`` (closed-loop: the next query waits for the
batch), this generator models real traffic: **Poisson arrivals at a
target rate**, each request fired at its scheduled instant whether or
not earlier ones completed — so queueing delay under overload shows up
in the latency tail instead of silently throttling the offered load
(no coordinated omission). Every request goes through the real server
process over HTTP, via :class:`repro.server.client.ServeClient`:

* latency is measured from the *scheduled arrival* (not the actual
  send) to the terminal event;
* TTFE is scheduled-arrival -> first streamed ``chunk`` event — the
  wire-level streaming SLO;
* goodput counts ``ok``/``limit`` completions per second of wall;
* traffic is spread across tenants (weighted round-robin), and
  per-tenant goodput yields a Jain fairness index normalized by the
  configured WFQ weights.

    python -m benchmarks.load_bench --smoke --launch          # CI leg
    python -m benchmarks.load_bench --launch                  # full:
        # rate ladder -> BENCH_serving.json["serving_tier"]
    python -m benchmarks.load_bench --host H --port P --rate 40
    python -m benchmarks.load_bench --smoke --launch --rate 0 # burst
        # (closed-loop worker pool; ab_gate.py's server_overhead leg)

``--launch`` owns the whole server lifecycle: spawn
``python -m repro.server.launch`` on a free port, wait for the READY
line, drive it, then SIGTERM (graceful drain) and reap — teardown runs
even when the bench fails.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import threading
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
_OUT = ROOT / "BENCH_serving.json"

# smoke shapes mirror serving_bench --smoke exactly, so the ab_gate
# server_overhead leg compares like against like (same graph, same
# query distribution, same engine knobs — only the wire differs)
SMOKE_GRAPH = ["--graph", "ba", "--graph-n", "128",
               "--graph-extra-edges", "128", "--graph-labels", "24",
               "--graph-seed", "0"]
SMOKE_ENGINE = ["--n-slots", "8", "--wave-size", "64", "--kpr", "8",
                "--limit", "1000", "--time-budget-s", "10"]
FULL_GRAPH = ["--graph", "ba", "--graph-n", "512",
              "--graph-extra-edges", "512", "--graph-labels", "24",
              "--graph-seed", "0"]
# two-tenant mix: alpha carries 2x the weight and 2x the traffic, so
# under WFQ both should see ~equal per-weight goodput (fairness ~1.0)
TENANTS = {"alpha": {"weight": 2.0}, "beta": {"weight": 1.0}}
TENANT_MIX = ["alpha", "alpha", "beta"]


def _build_queries(n_vertices: int, extra_edges: int, query_size: int,
                   n: int, seed: int = 7) -> list:
    from repro.data.graph_gen import ba_labeled_graph, query_set
    data = ba_labeled_graph(n_vertices, 3, 24, extra_edges=extra_edges,
                            seed=0)
    return query_set(data, query_size, n, seed=seed)


# ----------------------------------------------------------------------
# server lifecycle (--launch)
# ----------------------------------------------------------------------
def launch_server(extra_args: list[str], timeout_s: float = 600.0
                  ) -> tuple[subprocess.Popen, dict]:
    """Spawn ``python -m repro.server.launch`` and wait for its READY
    line. Caller must :func:`stop_server` the returned process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "repro.server.launch", "--port", "0",
           "--tenants", json.dumps(TENANTS), *extra_args]
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True)
    deadline = time.monotonic() + timeout_s
    while True:
        if proc.poll() is not None:
            raise RuntimeError(
                f"server exited early with code {proc.returncode}")
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError("server did not become ready in time")
        line = proc.stdout.readline()
        if line.startswith("REPRO_SERVER_READY "):
            return proc, json.loads(line.split(" ", 1)[1])


def stop_server(proc: subprocess.Popen, timeout_s: float = 60.0) -> int:
    """SIGTERM (graceful drain) then reap; SIGKILL past the timeout."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)
    if proc.stdout is not None:
        proc.stdout.close()
    return proc.returncode


# ----------------------------------------------------------------------
# the open-loop run
# ----------------------------------------------------------------------
def run_load(host: str, port: int, queries: list, *, rate: float,
             seed: int = 0, tenant_mix: list[str] | None = None,
             limit: int | None = None) -> dict:
    """Drive one open-loop run: ``len(queries)`` requests, Poisson
    arrivals at ``rate`` req/s. For ``rate <= 0`` this dispatches to
    :func:`run_burst` (closed-loop capacity probe — a bounded worker
    pool issuing back-to-back, used by the A/B overhead gate; one
    thread per request would measure client thread-spawn stagger, not
    server goodput)."""
    if rate <= 0:
        return run_burst(host, port, queries, tenant_mix=tenant_mix,
                         limit=limit)
    from repro.server.client import ServeClient, ServerError

    mix = tenant_mix or TENANT_MIX
    n = len(queries)
    rng = np.random.default_rng(seed)
    inter = rng.exponential(1.0 / rate, size=n)
    arrivals = np.cumsum(inter)
    client = ServeClient(host, port)
    records: list[dict] = [None] * n
    options = {} if limit is None else {"limit": limit}

    t0 = time.perf_counter()

    def worker(i: int) -> None:
        tenant = mix[i % len(mix)]
        t_sched = arrivals[i]
        delay = t0 + t_sched - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t_send = time.perf_counter() - t0
        rec = {"i": i, "tenant": tenant, "t_sched_s": float(t_sched),
               "send_delay_ms": (t_send - t_sched) * 1e3,
               "n_chunks": 0, "n_rows": 0, "ttfe_ms": None,
               "status": None, "error": None}
        try:
            for ev in client.stream(queries[i % len(queries)],
                                    tenant=tenant, options=options,
                                    request_id=i):
                now = time.perf_counter() - t0
                if ev["event"] == "chunk" and ev["rows"]:
                    if rec["n_chunks"] == 0:
                        rec["ttfe_ms"] = (now - t_sched) * 1e3
                    rec["n_chunks"] += 1
                    rec["n_rows"] += len(ev["rows"])
                elif ev["event"] == "done":
                    rec["status"] = ev["result"]["status"]
                    rec["latency_ms"] = (now - t_sched) * 1e3
                elif ev["event"] == "error":
                    rec["status"] = "error"
                    rec["error"] = f"{ev['code']}: {ev['message']}"
                    rec["latency_ms"] = (now - t_sched) * 1e3
        except (ServerError, OSError, Exception) as e:  # noqa: BLE001
            rec["status"] = "error"
            rec["error"] = repr(e)
            rec["latency_ms"] = (time.perf_counter() - t0
                                 - t_sched) * 1e3
        records[i] = rec

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return _aggregate(records, wall, mix, rate=rate,
                      offered_qps=(n / arrivals[-1]
                                   if rate > 0 and arrivals[-1] > 0
                                   else None))


def run_burst(host: str, port: int, queries: list, *,
              n_threads: int = 8, tenant_mix: list[str] | None = None,
              limit: int | None = None) -> dict:
    """Closed-loop capacity probe (``--rate 0``): ``n_threads`` workers,
    each with its own connection, issue requests back-to-back until
    ``len(queries)`` complete. Latency is send -> terminal event (no
    scheduled arrival — the closed loop has none). This is the wire
    side of the ``server_overhead`` ratio: peak goodput through HTTP +
    NDJSON + admission vs the engine's own in-process batch."""
    from repro.server.client import ServeClient, ServerError

    mix = tenant_mix or TENANT_MIX
    n = len(queries)
    records: list[dict] = [None] * n
    options = {} if limit is None else {"limit": limit}
    t0 = time.perf_counter()

    def worker(idxs: list[int]) -> None:
        client = ServeClient(host, port)
        for i in idxs:
            tenant = mix[i % len(mix)]
            t_send = time.perf_counter() - t0
            rec = {"i": i, "tenant": tenant, "t_sched_s": float(t_send),
                   "send_delay_ms": 0.0, "n_chunks": 0, "n_rows": 0,
                   "ttfe_ms": None, "status": None, "error": None}
            try:
                for ev in client.stream(queries[i], tenant=tenant,
                                        options=options, request_id=i):
                    now = time.perf_counter() - t0
                    if ev["event"] == "chunk" and ev["rows"]:
                        if rec["n_chunks"] == 0:
                            rec["ttfe_ms"] = (now - t_send) * 1e3
                        rec["n_chunks"] += 1
                        rec["n_rows"] += len(ev["rows"])
                    elif ev["event"] == "done":
                        rec["status"] = ev["result"]["status"]
                        rec["latency_ms"] = (now - t_send) * 1e3
                    elif ev["event"] == "error":
                        rec["status"] = "error"
                        rec["error"] = f"{ev['code']}: {ev['message']}"
                        rec["latency_ms"] = (now - t_send) * 1e3
            except (ServerError, OSError, Exception) as e:  # noqa: BLE001
                rec["status"] = "error"
                rec["error"] = repr(e)
                rec["latency_ms"] = (time.perf_counter() - t0
                                     - t_send) * 1e3
            records[i] = rec

    k = max(1, min(n_threads, n))
    shards = [list(range(w, n, k)) for w in range(k)]
    threads = [threading.Thread(target=worker, args=(s,), daemon=True)
               for s in shards if s]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return _aggregate(records, wall, mix, rate=0.0, offered_qps=None)


def _aggregate(records: list[dict], wall: float, mix: list[str], *,
               rate: float, offered_qps: float | None) -> dict:
    statuses: dict[str, int] = {}
    for r in records:
        statuses[r["status"]] = statuses.get(r["status"], 0) + 1
    good = [r for r in records if r["status"] in ("ok", "limit")]
    lat = np.asarray([r["latency_ms"] for r in records
                      if r.get("latency_ms") is not None])
    ttfe = np.asarray([r["ttfe_ms"] for r in records
                       if r["ttfe_ms"] is not None])

    per_tenant: dict[str, dict] = {}
    for name in sorted(set(mix)):
        rs = [r for r in records if r["tenant"] == name]
        g = [r for r in rs if r["status"] in ("ok", "limit")]
        tl = np.asarray([r["latency_ms"] for r in rs
                         if r.get("latency_ms") is not None])
        per_tenant[name] = {
            "n": len(rs), "completed": len(g),
            "goodput_qps": len(g) / wall if wall > 0 else 0.0,
            "shed": sum(r["status"] == "shed" for r in rs),
            "errors": sum(r["status"] == "error" for r in rs),
            "p50_ms": float(np.percentile(tl, 50)) if len(tl) else None,
            "p99_ms": float(np.percentile(tl, 99)) if len(tl) else None,
            "weight": TENANTS.get(name, {}).get("weight", 1.0),
        }
    # Jain's fairness over per-weight goodput: 1.0 = every tenant got
    # exactly its weighted share of the served throughput
    shares = np.asarray([t["goodput_qps"] / t["weight"]
                         for t in per_tenant.values()])
    fairness = (float(shares.sum() ** 2 / (len(shares)
                                           * (shares ** 2).sum()))
                if len(shares) and shares.sum() > 0 else None)

    return {
        "open_loop": rate > 0,
        "target_rate_qps": float(rate),
        "n_requests": len(records),
        "wall_time_s": wall,
        "offered_qps": offered_qps,
        "goodput_qps": len(good) / wall if wall > 0 else 0.0,
        "statuses": statuses,
        "shed": statuses.get("shed", 0),
        "errors": statuses.get("error", 0),
        "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
        "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
        "ttfe_p50_ms": (float(np.percentile(ttfe, 50))
                        if len(ttfe) else None),
        "ttfe_p99_ms": (float(np.percentile(ttfe, 99))
                        if len(ttfe) else None),
        "total_rows": int(sum(r["n_rows"] for r in records)),
        "per_tenant": per_tenant,
        "fairness_jain": fairness,
        "queries": records,
    }


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default=None,
                    help="target a running server (with --port)")
    ap.add_argument("--port", type=int, default=None)
    ap.add_argument("--launch", action="store_true",
                    help="spawn + tear down the server process here")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI run; never writes "
                         "BENCH_serving.json")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate (req/s); 0 = burst; "
                         "default: smoke 8.0, full ladder")
    ap.add_argument("--n-requests", type=int, default=None)
    ap.add_argument("--repeats", type=int, default=1,
                    help="reruns per rate, keeping the best-goodput "
                         "row (wave-level noise dominates the tiny "
                         "burst walls)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # the launched server enables the cache in its own process; this
    # sets the same directory here without starting a JAX backend, so
    # the parent never holds the chip its child needs
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.launch == (args.host is not None):
        ap.error("pass exactly one of --launch or --host/--port")
    if args.host is not None and args.port is None:
        ap.error("--host requires --port")

    if args.smoke:
        n_req = args.n_requests or 12
        graph_v, graph_e, qsize = 128, 128, 4
        server_args = SMOKE_GRAPH + SMOKE_ENGINE + [
            "--warmup-queries", "4", "--quiet"]
        rates = [args.rate if args.rate is not None else 8.0]
    else:
        n_req = args.n_requests or 64
        graph_v, graph_e, qsize = 512, 512, 6
        server_args = FULL_GRAPH + ["--limit", "1000",
                                    "--time-budget-s", "10", "--quiet"]
        rates = ([args.rate] if args.rate is not None
                 else [15.0, 40.0, 100.0])

    burst = len(rates) == 1 and rates[0] <= 0
    if burst:
        # mirror the server's in-process warmup baseline batch exactly
        # (same generator seed/size as MatchServer.warmup) so the
        # wire-vs-in-process overhead ratio compares identical work
        queries = _build_queries(graph_v, graph_e, 4, 8, seed=1)
    else:
        queries = _build_queries(graph_v, graph_e, qsize,
                                 min(n_req, 32))

    proc = None
    info = {}
    try:
        if args.launch:
            proc, info = launch_server(server_args)
            host, port = info["host"], info["port"]
        else:
            host, port = args.host, args.port

        runs = []
        for rate in rates:
            reqs = [queries[i % len(queries)] for i in range(n_req)]
            row = None
            for rep in range(max(args.repeats, 1)):
                cand = run_load(host, port, reqs, rate=rate,
                                seed=args.seed + rep)
                if row is None \
                        or cand["goodput_qps"] > row["goodput_qps"]:
                    row = cand
            runs.append(row)
            ttfe = row["ttfe_p50_ms"]
            print(f"# rate={rate:g}: goodput="
                  f"{row['goodput_qps']:.1f} qps "
                  f"p50={row['p50_ms']:.0f}ms "
                  f"ttfe_p50={ttfe if ttfe is None else round(ttfe)}ms "
                  f"shed={row['shed']} errors={row['errors']} "
                  f"fairness={row['fairness_jain']}", file=sys.stderr)

        from repro.server.client import ServeClient
        c = ServeClient(host, port)
        slo = c.slo()
        payload = runs[0] if len(runs) == 1 else {
            "open_loop": True,
            "rates": runs,
            # headline: the highest-goodput rung of the ladder
            "headline": max(runs, key=lambda r: r["goodput_qps"]),
        }
        payload["server"] = {"host": host, "port": port,
                             "launched": bool(args.launch)}
        payload["server_slo"] = slo
        if burst and info.get("baseline_qps"):
            # wire tax: burst goodput over the server's own in-process
            # baseline (same engine instance, same queries) — gated by
            # scripts/ab_gate.py's server_overhead leg
            payload["inprocess_qps"] = info["baseline_qps"]
            payload["server_overhead"] = (payload["goodput_qps"]
                                          / info["baseline_qps"])
            print(f"# server_overhead="
                  f"{payload['server_overhead']:.3f} "
                  f"(wire {payload['goodput_qps']:.1f} / in-process "
                  f"{payload['inprocess_qps']:.1f} qps)",
                  file=sys.stderr)
    finally:
        if proc is not None:
            code = stop_server(proc)
            if code not in (0, -signal.SIGTERM):
                print(f"# server exited with code {code}",
                      file=sys.stderr)

    if not args.smoke and _OUT.exists():
        bench = json.loads(_OUT.read_text())
        bench["serving_tier"] = payload
        _OUT.write_text(json.dumps(bench, indent=2) + "\n")
        print(f"# wrote serving_tier -> {_OUT}", file=sys.stderr)
    print(json.dumps(payload, indent=2))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    sys.exit(main())
