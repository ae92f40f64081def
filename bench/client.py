"""The load generator: a child process that never imports JAX.

    python bench/client.py        # spec on stdin, see ``main``

It reads one JSON line (host, port, traffic loop and parameters, the
seed, the request bodies and the warm-up bodies), sends the warm-up
bodies one at a time, prints ``ready``, then waits for a line
``go <t_begin> <t_end> <deadline>`` (``time.monotonic()`` seconds, the
same clock as the parent's). The traffic loop (``bench/loops/<loop>.py``)
sends requests from ``t_begin`` until ``t_end``; requests still in
flight at ``t_end`` run on until ``deadline``. The parent's window is
the end of that span. Then it writes one JSON
line of per-request records and, after it, the rows every request
received, as little-endian int32.

Each record holds the scheduled time, the send time, the time of the
first ``chunk`` that carried rows, the time of the terminal event, the
status (``ok``/``limit``/``timeout``/... from the ``done`` event,
``error:<code>`` from an ``error`` event, ``unfinished`` past the
deadline, ``client-error`` on a broken stream), how many rows came, and
the arrival time and row count of every chunk that carried rows.
"""
from __future__ import annotations

import http.client
import importlib.util
import json
import pathlib
import socket
import sys
import threading
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load_loop(name: str):
    """``bench/loops/<name>.py``, found by the traffic file's ``loop``."""
    path = HERE / "loops" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_loop_{name}",
                                                  path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no traffic loop {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def post_match(host: str, port: int, body: bytes, deadline: float,
               chunks: list | None = None
               ) -> tuple[str, list, float | None]:
    """POST one request and read its NDJSON stream up to ``deadline``.
    Returns (status, rows, time of the first chunk with rows); appends
    ``[time, rows]`` of every chunk with rows to ``chunks`` if given."""
    rows: list = []
    t_first = None
    left = deadline - time.monotonic()
    if left <= 0:
        return "unfinished", rows, t_first
    conn = http.client.HTTPConnection(host, port, timeout=left)
    try:
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        conn.request("POST", "/v1/match", body=body, headers={
            "Content-Type": "application/json",
            "Content-Length": str(len(body))})
        resp = conn.getresponse()
        while True:
            line = resp.readline()
            if not line:
                return "client-error", rows, t_first
            if not line.strip():
                continue
            ev = json.loads(line)
            kind = ev.get("event")
            if kind == "chunk" and ev["rows"]:
                t = time.monotonic()
                if t_first is None:
                    t_first = t
                if chunks is not None:
                    chunks.append([t, len(ev["rows"])])
                rows.extend(ev["rows"])
            elif kind == "done":
                return str(ev["result"]["status"]), rows, t_first
            elif kind == "error":
                return f"error:{ev.get('code')}", rows, t_first
    except (socket.timeout, TimeoutError):
        return "unfinished", rows, t_first
    except (OSError, http.client.HTTPException, ValueError, KeyError):
        return "client-error", rows, t_first
    finally:
        conn.close()


class Recorder:
    """``send(i, t_sched)``: run request ``i`` now, record it. Request
    ``i`` sends query ``i`` of the pool, from its start again once the
    pool is used up."""

    def __init__(self, host: str, port: int, bodies: list[bytes],
                 deadline: float):
        self.host, self.port = host, port
        self.bodies = bodies
        self.deadline = deadline
        self.records: dict[int, dict] = {}
        self.rows: dict[int, list] = {}
        self._lock = threading.Lock()

    def send(self, i: int, t_sched: float) -> None:
        t_send = time.monotonic()
        body = self.bodies[i % len(self.bodies)]
        chunks: list = []
        status, rows, t_first = post_match(self.host, self.port, body,
                                           self.deadline, chunks)
        t_done = time.monotonic()
        rec = {"i": i, "t_sched": t_sched, "t_send": t_send,
               "t_first": t_first, "t_done": t_done, "status": status,
               "n_rows": len(rows), "chunks": chunks}
        with self._lock:
            self.records[i] = rec
            self.rows[i] = rows


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    host, port = spec["host"], int(spec["port"])
    loop = load_loop(spec["loop"])
    warm_deadline = time.monotonic() + float(spec["warmup_timeout_s"])
    for body in spec["warmup_bodies"]:
        status, _, _ = post_match(host, port, body.encode(), warm_deadline)
        if status not in ("ok", "limit"):
            print(f"warm-up request ended {status}", file=sys.stderr)
            return 1
    print("ready", flush=True)
    go = sys.stdin.readline().split()
    if not go or go[0] != "go":
        return 1
    t_begin, t_end, deadline = (float(x) for x in go[1:4])
    rec = Recorder(host, port, [b.encode() for b in spec["bodies"]],
                   deadline)
    loop.drive(rec.send, spec["params"], int(spec["seed"]), t_begin, t_end,
               deadline)
    order = sorted(rec.records)
    blobs, meta = [], []
    for i in order:
        r = rec.records[i]
        arr = np.asarray(rec.rows[i], dtype="<i4").reshape(r["n_rows"], -1) \
            if r["n_rows"] else np.zeros((0, 0), "<i4")
        r["row_width"] = int(arr.shape[1]) if arr.size else 0
        blobs.append(arr.tobytes())
        meta.append(r)
    out = sys.stdout.buffer
    out.write(json.dumps({"records": meta}).encode() + b"\n")
    for b in blobs:
        out.write(b)
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
