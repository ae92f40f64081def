"""Serve subgraph-matching queries on one TPU chip and check every answer.

    python chip_smoke.py                 # every phase, both kernel backends
    python chip_smoke.py --phase hier    # one phase

Each phase serves one data graph through the production path — the
``MatchServer`` that ``python -m repro.server.launch`` runs, then
``QueryServer`` → ``WaveScheduler`` → device-resident stacks — once
under the ``jnp`` kernel backend and once under the compiled ``pallas``
kernels, with every engine knob left to resolve as in production. A
``ServeClient`` thread of this process sends 8 random-walk queries of 8
vertices and 8 of 16 vertices over HTTP. Phases:

* ``dense``: ``human_like_graph(seed=0)``, generated to the Human
  dataset's published 4,674 vertices, 86,282 edges and 44 labels, on
  the dense VMEM adjacency layout;
* ``hier``: ``powerlaw_graph(65536, 3, 16, seed=0)`` on the
  hierarchical HBM adjacency layout.

Every answer is compared with the host oracle ``backtrack_deadend`` at
the same limit: where the oracle finds fewer embeddings than the limit
the two sets are equal; otherwise the counts are equal and every row is
distinct and a valid embedding (labels match, query edges map to data
edges, the mapping is injective).

The run fails (non-zero exit, no result line) on a wrong answer, a
status other than ``ok``/``limit``, a moved fault counter, an
adjacency layout other than the phase's, or a default device that is
not a TPU. Its last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Compiled programs persist in the compilation cache
(``repro.compile_cache``), so a second run in the same checkout skips
most compilation.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402

BACKENDS = ("jnp", "pallas")
QUERY_SIZES = (8, 16)
QUERIES_PER_SIZE = 8
CLIENT_TIMEOUT_S = 600.0
# Each request asks for no time budget: the oracle runs without one, and
# an answer cut short by the launcher's default budget (10 s) cannot be
# compared. How long the answers take is printed, not judged.
REQUEST_OPTIONS = {"time_budget_s": None}


def _human_like():
    from repro.data.graph_gen import human_like_graph
    return human_like_graph(seed=0)


def _powerlaw_64k():
    from repro.data.graph_gen import powerlaw_graph
    return powerlaw_graph(65536, 3, 16, seed=0)


# phase -> (data graph builder, adjacency layout it must run on)
PHASES = {
    "dense": (_human_like, "dense-vmem"),
    "hier": (_powerlaw_64k, "hier-hbm"),
}


def invalid_embedding(query, data, row: tuple) -> str | None:
    """Why ``row`` (query vertex i -> data vertex row[i]) is not an
    embedding of ``query`` in ``data``, or None."""
    if len(row) != query.n:
        return f"row has {len(row)} vertices, query has {query.n}"
    if len(set(row)) != len(row):
        return "mapping is not injective"
    for i, v in enumerate(row):
        if data.labels[v] != query.labels[i]:
            return f"query vertex {i} label differs from data vertex {v}"
    for a in range(query.n):
        for b in query.neighbors(a):
            if a < b and not data.has_edge(row[a], row[int(b)]):
                return f"query edge ({a}, {int(b)}) maps to a non-edge"
    return None


def check_answer(query, data, rows, status: str, oracle,
                 limit: int | None) -> str | None:
    """Why a served answer disagrees with the oracle's, or None."""
    if status not in ("ok", "limit"):
        return f"status {status!r}"
    got = [tuple(int(v) for v in r) for r in rows]
    want = {tuple(int(v) for v in e) for e in oracle.embeddings}
    if limit is None or oracle.stats.found < limit:
        if len(got) != len(want) or set(got) != want:
            return (f"embedding set differs from the oracle's "
                    f"({len(got)} rows vs {len(want)})")
        return None
    if len(got) != oracle.stats.found:
        return f"{len(got)} embeddings, oracle found {oracle.stats.found}"
    if len(set(got)) != len(got):
        return "duplicate embedding rows"
    for r in got:
        why = invalid_embedding(query, data, r)
        if why is not None:
            return f"invalid embedding {r}: {why}"
    return None


def serve_and_check(data, queries, oracles, backend: str,
                    variant: str) -> tuple[dict, list[str]]:
    """Serve ``queries`` through a fresh ``MatchServer`` under kernel
    ``backend`` and check the answers. Returns (report, failures)."""
    import jax
    from repro.kernels.config import backend_scope
    from repro.server import MatchServer, ServerArgs
    from repro.server.client import ServeClient

    failures: list[str] = []
    args = ServerArgs(port=0)
    with backend_scope(backend):
        t0 = time.perf_counter()
        server = MatchServer(data, args)
        try:
            server.warmup()
            warmup_s = time.perf_counter() - t0
            server.start()
            answers: list = [None] * len(queries)
            errors: list[BaseException] = []

            def client() -> None:
                try:
                    c = ServeClient(server.host, server.port,
                                    timeout=CLIENT_TIMEOUT_S)
                    for i, q in enumerate(queries):
                        t = time.perf_counter()
                        rows, res = c.match(q, options=REQUEST_OPTIONS)
                        answers[i] = (rows, res, time.perf_counter() - t)
                except BaseException as e:    # noqa: BLE001 — re-raised
                    errors.append(e)

            th = threading.Thread(target=client, name="smoke-client")
            th.start()
            th.join(timeout=CLIENT_TIMEOUT_S * 2)
            if th.is_alive():
                failures.append("client thread did not finish")
            if errors:
                raise errors[0]
        finally:
            server.shutdown(drain=True)
    if server.error is not None:
        failures.append(f"engine failed: {server.error!r}")
    stats = server.qserver.scheduler.scheduler_stats()
    moved = {k: v for k, v in stats["faults"].items() if v}
    if moved:
        failures.append(f"fault counters moved: {moved}")
    if stats["adjacency_variant"] != variant:
        failures.append(f"adjacency {stats['adjacency_variant']!r}, "
                        f"expected {variant!r}")
    lat_ms = []
    for i, (q, oracle) in enumerate(zip(queries, oracles)):
        if answers[i] is None:
            failures.append(f"query {i}: no answer")
            continue
        rows, res, dt = answers[i]
        lat_ms.append(round(dt * 1e3, 3))
        why = check_answer(q, data, rows, res["status"], oracle,
                           args.limit)
        if why is not None:
            failures.append(f"query {i} (n={q.n}): {why}")
    mem = jax.devices()[0].memory_stats() or {}
    report = {
        "adjacency_variant": stats["adjacency_variant"],
        "warmup_s": round(warmup_s, 3),
        "latency_ms": lat_ms,
        "peak_bytes_in_use": mem.get("peak_bytes_in_use"),
        "waves": stats["waves"], "mean_occupancy": stats["mean_occupancy"],
        # scheduler's host-clock split, warmup included: enqueueing
        # dispatches / blocked reading their digests / host bookkeeping
        "host_clock_s": {k: round(stats[f"{k}_time_s"], 3) for k in
                         ("dispatch", "device_sync", "host")},
        "tuning": stats["tuning"]["source"],
        "knobs": {k: stats[k] for k in ("n_slots", "wave_size",
                                        "megastep_depth")},
    }
    return report, failures


def run_phase(name: str, backends=BACKENDS) -> list[str]:
    """Build one phase's graph, queries and oracle answers, then serve
    them under each backend. Prints one line per backend; returns the
    failures."""
    from repro.core.backtrack import backtrack_deadend
    from repro.data.graph_gen import query_set
    from repro.server import ServerArgs

    build, variant = PHASES[name]
    t0 = time.perf_counter()
    data = build()
    queries = [q for size in QUERY_SIZES
               for q in query_set(data, size, QUERIES_PER_SIZE, seed=0)]
    limit = ServerArgs().limit
    oracles = [backtrack_deadend(q, data, limit=limit) for q in queries]
    print(f"phase {name}: |V|={data.n} |E|={data.n_edges} "
          f"labels={data.n_labels}, {len(queries)} queries, oracle at "
          f"limit {limit} ({time.perf_counter() - t0:.1f}s)", flush=True)
    failures = []
    for backend in backends:
        report, fails = serve_and_check(data, queries, oracles, backend,
                                        variant)
        print(f"phase {name} backend {backend}: " + json.dumps(report),
              flush=True)
        failures += [f"{name}/{backend}: {f}" for f in fails]
    return failures


def _cache_entries(path: str) -> int:
    p = pathlib.Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serve queries on one TPU chip; check every answer.")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help="run one phase (default: all)")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's default device is "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 2
    n_cached = _cache_entries(cache)
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{cache} holds {n_cached} entries", flush=True)

    t0 = time.perf_counter()
    failures = []
    for name in ([args.phase] if args.phase else list(PHASES)):
        failures += run_phase(name)
    print(f"done in {time.perf_counter() - t0:.1f}s; compile cache now "
          f"holds {_cache_entries(cache)} entries (was {n_cached})",
          flush=True)
    if failures:
        for f in failures:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
