"""Network serving tier (DESIGN.md §10): a standalone server process in
front of the in-process engine.

* :mod:`repro.server.server_args` — CLI-parseable :class:`ServerArgs`
  (host/port, data-graph spec, engine knobs resolved through
  ``MatchOptions``/the tuning cache, tenant admission config);
* :mod:`repro.server.protocol` — the versioned JSON wire encoding
  (query graphs, per-query options, streamed embedding chunks, terminal
  results carrying every ``Status``, typed errors);
* :mod:`repro.server.server` — the HTTP request loop over
  ``MatchSession``: one engine thread owns the scheduler, handler
  threads stream NDJSON events, client disconnects ride the eviction
  path, SIGTERM drains gracefully;
* :mod:`repro.server.admission` — multi-tenant admission: per-tenant
  token buckets, weighted fair queueing, bounded-queue load shedding;
* :mod:`repro.server.metrics` — the ``/metrics`` + ``/slo`` exporter;
* :mod:`repro.server.client` — the stdlib blocking/streaming client
  used by tests, examples and ``benchmarks/load_bench.py``. Importing
  it (or this package) starts no JAX backend; ``MatchServer`` loads
  the engine on first access.

Launch:  ``python -m repro.server.launch --graph ba --port 8421``
"""
from .admission import AdmissionController, TenantConfig
from .client import ServeClient
from .protocol import (ProtocolError, WIRE_VERSION, decode_event,
                       decode_query, encode_event, encode_query)
from .server_args import ServerArgs

__all__ = [
    "AdmissionController", "TenantConfig", "ServeClient",
    "ProtocolError", "WIRE_VERSION", "decode_event", "decode_query",
    "encode_event", "encode_query", "MatchServer", "ServerArgs",
]


def __getattr__(name):
    # MatchServer pulls in the engine, whose first device array starts
    # a JAX backend — a process that only imports the client (a load
    # generator) must not take the chip from its server
    if name == "MatchServer":
        from .server import MatchServer
        return MatchServer
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
