"""Network serving tier (DESIGN.md §10): wire protocol round-trips,
multi-tenant admission (WFQ / token buckets / bounded-queue shedding),
and end-to-end subprocess tests — streamed embeddings over HTTP must be
bit-identical to the in-process oracle, and a client disconnect must
cancel its query through the eviction path without disturbing
co-resident queries."""
import json
import signal
import subprocess
import sys
import threading
import time
import types
from pathlib import Path

import numpy as np
import pytest

from repro.api.handle import STATUSES
from repro.core.backtrack import backtrack_deadend
from repro.core.graph import Graph
from repro.data.graph_gen import ba_labeled_graph, query_set
from repro.server.admission import (AdmissionController, TenantConfig,
                                    TokenBucket)
from repro.server.client import ServeClient
from repro.server.protocol import (MatchRequestWire, ProtocolError,
                                   decode_event, decode_query,
                                   done_event, encode_event,
                                   encode_query)

ROOT = Path(__file__).resolve().parent.parent


def embset(embs):
    return set(frozenset(enumerate(e.tolist())) for e in embs)


def rowset(rows):
    return set(frozenset(enumerate(r)) for r in rows)


# ======================================================================
# protocol: versioned JSON wire encoding
# ======================================================================
def _tiny_query() -> Graph:
    return Graph.from_edges(3, [(0, 1), (1, 2)], [0, 1, 0], n_labels=2)


def test_query_roundtrip():
    q = _tiny_query()
    d = encode_query(q)
    q2 = decode_query(d)
    assert encode_query(q2) == d


def test_request_roundtrip():
    wire = MatchRequestWire(query=_tiny_query(), tenant="alpha",
                            options={"limit": 10, "priority": 3},
                            request_id="req-7")
    back = MatchRequestWire.from_json(wire.to_json())
    assert back.tenant == "alpha"
    assert back.options == {"limit": 10, "priority": 3}
    assert back.request_id == "req-7"
    assert encode_query(back.query) == encode_query(wire.query)


def test_every_terminal_status_survives_the_wire():
    """``error`` and ``shed`` included: no outcome is expressible
    in-process but not on the wire."""
    assert set(STATUSES) == {"ok", "limit", "timeout", "cancelled",
                             "error", "shed"}
    for st in STATUSES:
        ev = done_event(7, {"status": st, "n_embeddings": 0})
        back = decode_event(encode_event(ev))
        assert back == ev
        assert back["result"]["status"] == st


def test_done_event_rejects_non_terminal_status():
    with pytest.raises(ProtocolError):
        done_event(7, {"status": "running"})


def _valid_request() -> dict:
    return MatchRequestWire(query=_tiny_query()).to_wire()


@pytest.mark.parametrize("mutate", [
    pytest.param(lambda p: p.pop("v"), id="missing-version"),
    pytest.param(lambda p: p.update(v=99), id="wrong-version"),
    pytest.param(lambda p: p.pop("query"), id="missing-query"),
    pytest.param(lambda p: p["query"].update(n=0), id="n-zero"),
    pytest.param(lambda p: p["query"].update(n=65), id="n-too-big"),
    pytest.param(lambda p: p["query"].update(n="3"), id="n-not-int"),
    pytest.param(lambda p: p["query"].update(labels=[0, 1]),
                 id="labels-wrong-length"),
    pytest.param(lambda p: p["query"].update(labels=[0, -1, 0]),
                 id="negative-label"),
    pytest.param(lambda p: p["query"]["edges"].append([2, 2]),
                 id="self-loop"),
    pytest.param(lambda p: p["query"]["edges"].append([0, 3]),
                 id="edge-out-of-range"),
    pytest.param(lambda p: p["query"]["edges"].append([0]),
                 id="edge-not-a-pair"),
    pytest.param(lambda p: p["query"].update(n_labels=1),
                 id="n_labels-below-max-label"),
    pytest.param(lambda p: p.update(options={"wave_size": 9}),
                 id="engine-knob-not-settable"),
    pytest.param(lambda p: p.update(options={"limit": [1]}),
                 id="option-not-a-scalar"),
    pytest.param(lambda p: p.update(tenant=""), id="empty-tenant"),
    pytest.param(lambda p: p.update(tenant=7), id="tenant-not-str"),
    pytest.param(lambda p: p.update(request_id={"a": 1}),
                 id="request_id-not-scalar"),
])
def test_malformed_request_rejected(mutate):
    payload = _valid_request()
    mutate(payload)
    with pytest.raises(ProtocolError):
        MatchRequestWire.from_json(json.dumps(payload))


def test_request_not_json_rejected():
    with pytest.raises(ProtocolError):
        MatchRequestWire.from_json(b"{nope")


@pytest.mark.parametrize("line", [
    pytest.param('{"v": 1, "event": "nope"}', id="unknown-kind"),
    pytest.param('{"event": "done"}', id="event-missing-version"),
    pytest.param('{"v": 1, "event": "chunk", "seq": -1, "rows": []}',
                 id="negative-seq"),
    pytest.param('{"v": 1, "event": "chunk", "seq": 0, "rows": [[1.5]]}',
                 id="non-int-rows"),
    pytest.param('{"v": 1, "event": "done", "result": '
                 '{"status": "running"}}', id="done-non-terminal"),
    pytest.param('{"v": 1, "event": "error", "message": "x"}',
                 id="error-missing-code"),
    pytest.param("{not json", id="not-json"),
])
def test_malformed_event_rejected(line):
    with pytest.raises(ProtocolError):
        decode_event(line)


# ======================================================================
# admission: WFQ, token buckets, bounded-queue shedding
# ======================================================================
def _item(priority=0, name=""):
    return types.SimpleNamespace(priority=priority, name=name)


def test_wfq_shares_interleave_by_weight():
    """Both tenants backlogged: weight-2 alpha gets exactly 2 of every
    3 admissions, and weight-1 beta is never starved — finish tags are
    frozen at enqueue, not re-priced per pop."""
    ctl = AdmissionController({
        "alpha": TenantConfig(weight=2.0),
        "beta": TenantConfig(weight=1.0)})
    for i in range(6):
        ctl.offer(_item(name=f"a{i}"), "alpha")
    for i in range(3):
        ctl.offer(_item(name=f"b{i}"), "beta")
    order = [ctl.next_ready().name[0] for _ in range(9)]
    assert order == ["a", "a", "b"] * 3
    assert ctl.next_ready() is None


def test_token_bucket_rate_and_burst():
    b = TokenBucket(rate=10.0, burst=2.0, now=0.0)
    assert b.take(0.0) and b.take(0.0)        # burst capacity
    assert not b.take(0.0)                    # empty
    assert not b.peek(0.05)                   # half a token refilled
    assert b.peek(0.1) and b.take(0.1)        # one token back at 10/s
    assert not b.take(0.1)
    unlimited = TokenBucket(rate=None, burst=1.0, now=0.0)
    assert all(unlimited.take(0.0) for _ in range(100))


def test_over_rate_tenant_waits_without_blocking_others():
    ctl = AdmissionController({
        "slow": TenantConfig(rate=0.001, burst=1.0),
        "fast": TenantConfig()})
    ctl.offer(_item(name="s0"), "slow")
    ctl.offer(_item(name="s1"), "slow")
    ctl.offer(_item(name="f0"), "fast")
    got = {ctl.next_ready().name, ctl.next_ready().name}
    assert got == {"s0", "f0"}        # slow spent its one token
    assert ctl.next_ready() is None   # s1 gated, not admissible
    assert ctl.snapshot()["slow"]["pending"] == 1


def test_bounded_queue_sheds_lowest_priority():
    shed = []
    ctl = AdmissionController(
        {"t": TenantConfig(max_pending=2)}, on_shed=shed.append)
    ctl.offer(_item(priority=1, name="p1"), "t")
    ctl.offer(_item(priority=2, name="p2"), "t")
    # new arrival is itself the lowest: shed on arrival, offer -> False
    assert ctl.offer(_item(priority=0, name="p0"), "t") is False
    assert [it.name for it in shed] == ["p0"]
    # higher-priority arrival displaces the current lowest
    assert ctl.offer(_item(priority=3, name="p3"), "t") is True
    assert [it.name for it in shed] == ["p0", "p1"]
    assert ctl.snapshot()["t"]["shed"] == 2
    kept = {ctl.next_ready().name, ctl.next_ready().name}
    assert kept == {"p2", "p3"}


def test_requeue_front_counts_backpressure_not_shed():
    ctl = AdmissionController({"t": TenantConfig()})
    ctl.offer(_item(name="x"), "t")
    ctl.offer(_item(name="y"), "t")
    it = ctl.next_ready()
    assert it.name == "x"
    ctl.requeue_front(it, "t")               # engine said QueueFull
    snap = ctl.snapshot()["t"]
    assert snap["backpressure"] == 1
    assert snap["admitted"] == 0
    assert snap["shed"] == 0
    assert ctl.next_ready().name == "x"      # head of the line again
    assert ctl.next_ready().name == "y"


# ======================================================================
# end to end: subprocess server over HTTP
# ======================================================================
GRAPH = dict(n=96, m=3, labels=3, extra=96, seed=3)
SERVER_ARGS = ["--graph", "ba", "--graph-n", "96", "--graph-m", "3",
               "--graph-labels", "3", "--graph-extra-edges", "96",
               "--graph-seed", "3", "--n-slots", "8",
               "--wave-size", "64", "--kpr", "8",
               "--warmup-queries", "2", "--quiet", "--port", "0",
               "--tenants",
               json.dumps({"alpha": {"weight": 2.0},
                           "beta": {"weight": 1.0}})]


@pytest.fixture(scope="module")
def served():
    """One server subprocess for the whole module + the identical graph
    rebuilt in-process for the oracle (build_graph is deterministic in
    (kind, n, seed))."""
    data = ba_labeled_graph(GRAPH["n"], GRAPH["m"], GRAPH["labels"],
                            extra_edges=GRAPH["extra"],
                            seed=GRAPH["seed"])
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.server.launch", *SERVER_ARGS],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True)
    info = None
    deadline = time.monotonic() + 600
    try:
        while info is None:
            assert proc.poll() is None, "server died during startup"
            assert time.monotonic() < deadline, "server never ready"
            line = proc.stdout.readline()
            if line.startswith("REPRO_SERVER_READY "):
                info = json.loads(line.split(" ", 1)[1])
        yield data, ServeClient(info["host"], info["port"])
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)   # graceful drain
            proc.wait(timeout=120)
        proc.stdout.close()
    assert proc.returncode == 0               # drain exits clean


def test_e2e_two_tenant_streams_match_oracle(served):
    """Six queries streamed concurrently across two tenants: every
    stream opens with ``accepted``, chunks carry increasing ``seq``,
    and the chunk-row union is bit-identical to the in-process
    oracle."""
    data, cli = served
    queries = query_set(data, 4, 6, seed=21)
    oracle = [embset(backtrack_deadend(q, data, limit=None).embeddings)
              for q in queries]
    out = [None] * len(queries)

    def drive(i):
        tenant = "alpha" if i % 2 == 0 else "beta"
        rows, seqs, status = [], [], None
        first = None
        for ev in cli.stream(queries[i], tenant=tenant,
                             options={"limit": None}, request_id=i):
            if first is None:
                first = ev["event"]
            if ev["event"] == "chunk":
                seqs.append(ev["seq"])
                rows.extend(ev["rows"])
            elif ev["event"] == "done":
                status = ev["result"]["status"]
                assert ev["result"]["request_id"] == i
                assert ev["result"]["tenant"] == tenant
        out[i] = (first, rows, seqs, status)

    threads = [threading.Thread(target=drive, args=(i,))
               for i in range(len(queries))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for i, (first, rows, seqs, status) in enumerate(out):
        assert first == "accepted"
        assert status == "ok"
        assert seqs == sorted(seqs)
        assert rowset(rows) == oracle[i], f"query {i} diverged"


def test_e2e_blocking_client_matches_oracle(served):
    data, cli = served
    q = query_set(data, 4, 6, seed=21)[2]
    rows, res = cli.match(q, options={"limit": None})
    ref = backtrack_deadend(q, data, limit=None)
    assert res["status"] == "ok"
    assert embset(rows) == embset(ref.embeddings)


def test_e2e_disconnect_cancels_without_disturbing_residents(served):
    """Drop the connection mid-stream on a heavy query: the server
    must cancel it through the eviction path (client_disconnects and
    ``cancelled`` both observable), and a query running right through
    the eviction window still returns the exact oracle set."""
    data, cli = served
    heavy = query_set(data, 6, 4, seed=33)[0]   # ~0.5s at limit=None
    light = query_set(data, 4, 6, seed=21)[3]
    ref = backtrack_deadend(light, data, limit=None)

    before = cli.metrics()["wire"].get("client_disconnects", 0)
    it = cli.stream(heavy, tenant="alpha", options={"limit": None})
    for ev in it:
        if ev["event"] == "chunk" and ev["rows"]:
            break                    # heavy query is mid-enumeration
        assert ev["event"] != "done", "heavy query finished too fast"
    it.close()                       # drops the TCP connection

    # co-resident with the eviction: exactness must be unaffected
    rows, res = cli.match(light, tenant="beta",
                          options={"limit": None})
    assert res["status"] == "ok"
    assert embset(rows) == embset(ref.embeddings)

    deadline = time.monotonic() + 30
    while True:
        m = cli.metrics()
        slo = cli.slo()
        if (m["wire"].get("client_disconnects", 0) > before
                and slo.get("cancelled", 0) >= 1):
            break
        assert time.monotonic() < deadline, (
            f"no cancellation observed: wire={m['wire']} slo={slo}")
        time.sleep(0.2)


def test_e2e_slo_and_metrics_shape(served):
    _, cli = served
    assert cli.health()["ok"] is True
    slo = cli.slo()
    for k in ("queue_depth", "resident_queries",
              "backpressure_absorbed"):
        assert isinstance(slo[k], int) and slo[k] >= 0
    m = cli.metrics()
    assert set(m["tenants"]) >= {"alpha", "beta"}
    for t in m["tenants"].values():
        assert t["offered"] >= t["admitted"] >= 0


# ======================================================================
# one process per chip, and failures that must stop the server
# ======================================================================
def _subprocess_env(**extra) -> dict:
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.update(extra)
    return env


def test_client_import_starts_no_jax_backend():
    """A load generator imports the client and must not hold the chip
    its server process needs: importing the client, the protocol and
    the package creates no JAX backend (checked in a fresh
    interpreter)."""
    code = ("import repro.server, repro.server.client, "
            "repro.server.protocol\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "import repro.core.vectorized\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('no-backend')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         env=_subprocess_env(), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "no-backend"


def test_launch_exits_nonzero_when_a_kernel_fails_to_compile():
    """The compiled Pallas backend cannot lower for the CPU: the
    server's warmup raises, the process exits non-zero and never
    announces readiness."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.server.launch", *SERVER_ARGS],
        cwd=ROOT, env=_subprocess_env(REPRO_KERNEL_BACKEND="pallas"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode != 0
    assert "REPRO_SERVER_READY" not in out.stdout
    assert "Only interpret mode" in out.stderr


def _small_server(**knobs):
    from repro.server import MatchServer, ServerArgs
    data = ba_labeled_graph(GRAPH["n"], GRAPH["m"], GRAPH["labels"],
                            extra_edges=GRAPH["extra"],
                            seed=GRAPH["seed"])
    args = ServerArgs(port=0, n_slots=2, wave_size=32, kpr=8, **knobs)
    return data, MatchServer(data, args)


def test_warmup_raises_when_a_fault_counter_moves():
    """A warmup the engine only finished by retrying or falling back
    to the host is a failed warmup: the server must not go ready."""
    from repro.core.faults import FaultPlan, FaultSpec
    _, srv = _small_server(warmup_queries=2)
    try:
        srv.qserver.scheduler._faults = FaultPlan(
            [FaultSpec("dispatch", "exception", at=1)])
        with pytest.raises(RuntimeError, match="warmup failed"):
            srv.warmup()
    finally:
        srv.httpd.server_close()


def test_engine_failure_ends_requests_and_serve_forever_raises():
    """An engine step that raises while serving (a program that fails
    to compile for a new shape) ends the live request with status
    ``error``, stops the listener and makes serve_forever raise, so
    ``repro.server.launch`` exits non-zero."""
    data, srv = _small_server(warmup_queries=0)

    def broken_step():
        raise RuntimeError("device program failed to compile")

    srv.qserver.session.step = broken_step
    raised: list[BaseException] = []

    def serve():
        try:
            srv.serve_forever()
        except RuntimeError as e:
            raised.append(e)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    try:
        q = query_set(data, 4, 1, seed=21)[0]
        _, res = ServeClient(srv.host, srv.port, timeout=60).match(q)
        assert res["status"] == "error"
        assert "failed to compile" in res["error"]
        t.join(timeout=60)
        assert not t.is_alive()
        assert raised and "failed to compile" in str(raised[0].__cause__)
    finally:
        srv.shutdown(drain=False)
        srv.httpd.server_close()
