"""Split a profiler trace by the program's own names: device time by the
megastep's phase scopes, device idle time by the host span the engine
thread was in.

    python bench/trace_spans.py <file.xplane.pb>     # prints the split

:func:`load_xplane` reads the trace as ``trace_reduce.load_xplane``
does, and gives an event a fourth element where it has one: for a
device operation ``{"tf_op": <op_name>}``, the HLO ``op_name`` metadata
that carries the ``jax.named_scope`` path (``jit(f)/mega.select/while/
body/mega.refine/...:``); for a host annotation its arguments
(``{"query_id": 7}``). The profiler's Python API gives an event only its
own statistics, and a TPU keeps ``tf_op`` among those of the event's
metadata, so the metadata is read from the file itself.

:func:`reduce_spans` works on that plain data alone:

* ``scopes``: device self time per ``mega.*`` scope, the innermost one
  in the operation's ``tf_op``: an operation's duration less that of
  the operations nested in it on the same line (a loop encloses its
  body's operations);
* ``idle_by_span``: device idle seconds in the traced slice per
  innermost program span of the engine thread, the host line that
  holds the ``sched.step`` spans; idle time outside every span goes to
  ``unattributed``;
* ``idle_gaps``: the longest idle gaps, each named by that span and by
  what ``trace_reduce.name_gap`` finds on the host.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
import warnings

if __package__ in (None, ""):
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[1]))

from bench.trace_reduce import (  # noqa: E402
    MODULES_LINE, OPS_LINE, _line, _union, module_name, name_gap,
    traced_window)

# the engine's host spans (repro.core.spans): server.*, sched.*,
# store.*, metrics.*
PROGRAM_SPAN = re.compile(r"^(server|sched|store|metrics)\.[a-z_]+$")
STEP_SPAN = "sched.step"
SCOPE = re.compile(r"(?:^|/)(mega\.[a-z_]+)(?=[/:]|$)")
UNATTRIBUTED = "unattributed"
MEGASTEP = "run_device_megastep"


# ----------------------------------------------------------------------
# the file: events as trace_reduce reads them, plus what they carry
# ----------------------------------------------------------------------
def _varint(buf, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind == 1:
            i += 8
            continue
        elif kind == 5:
            i += 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind} not supported")
        yield key >> 3, v


def _first_varint(buf) -> int:
    """Field 1 of a message whose first field it is (XEvent's
    ``metadata_id``), else 0."""
    for f, v in _fields(buf):
        return v if f == 1 else 0
    return 0


def _tf_ops(plane) -> tuple[str, list]:
    """The plane's name and, for a device plane, the ``tf_op`` statistic
    of each ``XLA Ops`` event's metadata in file order (None where it
    has none). XPlane: name 2, lines 3, event_metadata 4, stat_metadata
    5; XLine: name 2, events 4; XEvent: metadata_id 1; XEventMetadata:
    stats 5; XStat: metadata_id 1, str_value 5, ref_value 7;
    XStatMetadata: name 2. A message writes its fields in the order of
    their numbers, so a name is read before what it names."""
    name, ops, ev_meta, stat_names = "", [], {}, {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
            if not name.startswith("/device:"):
                break
        elif f == 3:
            is_ops = False
            for g, e in _fields(v):
                if g == 2:
                    is_ops = bytes(e).decode() == OPS_LINE
                    if not is_ops:
                        break
                elif g == 4 and is_ops:
                    ops.append(_first_varint(e))
        elif f in (4, 5):
            entry = dict(_fields(v))
            val = entry.get(2, b"")
            if f == 4:
                ev_meta[entry.get(1, 0)] = [dict(_fields(st))
                                            for g, st in _fields(val)
                                            if g == 5]
            else:
                stat_names[entry.get(1, 0)] = bytes(
                    dict(_fields(val)).get(2, b"")).decode()
    tf_op = next((k for k, n in stat_names.items() if n == "tf_op"), None)
    found = {}
    for mid, stats in ev_meta.items():
        for st in stats:
            if st.get(1) == tf_op:
                if 5 in st:
                    found[mid] = bytes(st[5]).decode()
                elif 7 in st:
                    found[mid] = stat_names.get(st[7])
    return name, [found.get(m) for m in ops]


def load_xplane(path: str) -> dict:
    """``trace_reduce.load_xplane``'s planes, lines and events, with the
    fourth element described in the module's doc."""
    from jax.profiler import ProfileData
    with open(path, "rb") as fh:
        raw = memoryview(fh.read())
    tf_ops = dict(_tf_ops(v) for f, v in _fields(raw) if f == 1)
    planes = []
    with warnings.catch_warnings():    # the profiler's stats type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        for pl in ProfileData.from_file(str(path)).planes:
            lines = []
            host = pl.name.startswith("/host:")
            for ln in pl.lines:
                names = (tf_ops.get(pl.name, [])
                         if ln.name == OPS_LINE else None)
                ev = []
                for k, e in enumerate(ln.events):
                    row = [e.name, int(e.start_ns), int(e.duration_ns)]
                    if names is not None:
                        extra = ({"tf_op": names[k]}
                                 if k < len(names) and names[k] else None)
                    elif host:
                        extra = {a: b for a, b in e.stats
                                 if not a.startswith("_")} or None
                    else:
                        extra = None
                    if extra:
                        row.append(extra)
                    ev.append(row)
                if ev:
                    lines.append({"name": ln.name, "events": ev})
            if lines:
                planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


# ----------------------------------------------------------------------
# the reduction
# ----------------------------------------------------------------------
def scope_of(event: list) -> str | None:
    """The innermost ``mega.*`` scope in an operation's ``tf_op``."""
    if len(event) < 4 or not event[3].get("tf_op"):
        return None
    found = SCOPE.findall(event[3]["tf_op"])
    return found[-1] if found else None


def self_times(events: list) -> list[int]:
    """Each event's duration less the durations of the events nested
    directly in it (same line: each nests fully or not at all)."""
    order = sorted(range(len(events)),
                   key=lambda k: (events[k][1], -events[k][2]))
    own = [e[2] for e in events]
    stack: list[int] = []
    for k in order:
        s, d = events[k][1], events[k][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(s + d, events[p][1] + events[p][2]) - s
        stack.append(k)
    return own


def innermost(spans: list) -> list[tuple[int, int, str]]:
    """The host timeline cut into ``(start, end, name)`` pieces, each
    named by the innermost span that holds it; no piece where no span
    is open."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, str]] = []       # (end, name)
    cursor = None

    def emit(upto: int) -> None:
        nonlocal cursor
        if stack and cursor is not None and upto > cursor:
            out.append((cursor, upto, stack[-1][1]))
        cursor = upto

    for name, s, d in sorted(((e[0], e[1], e[2]) for e in spans),
                             key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((s + d, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def engine_line(trace: dict) -> list | None:
    """The program spans of the host line with the most ``sched.step``
    spans; None when no line has one."""
    best, count = None, 0
    for p in trace["planes"]:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            c = sum(1 for e in ln["events"] if e[0] == STEP_SPAN)
            if c > count:
                best, count = ln["events"], c
    if best is None:
        return None
    return [e for e in best if PROGRAM_SPAN.match(e[0])]


def _gaps(events: list, t0: int, t1: int) -> list[tuple[int, int]]:
    merged = [(max(s, t0), min(e, t1)) for s, e in
              _union([(e[1], e[1] + e[2]) for e in events])
              if e > t0 and s < t1]
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def _split(gap: tuple[int, int], pieces: list, starts: list
           ) -> dict[str, int]:
    """Nanoseconds of ``gap`` in each named piece (sorted, disjoint;
    ``starts`` their starts), the rest ``unattributed``."""
    s, e = gap
    out: dict[str, int] = {}
    covered = 0
    k = max(0, bisect.bisect_right(starts, s) - 1)
    while k < len(pieces) and pieces[k][0] < e:
        ps, pe, name = pieces[k]
        o = min(e, pe) - max(s, ps)
        if o > 0:
            out[name] = out.get(name, 0) + o
            covered += o
        k += 1
    if e - s > covered:
        out[UNATTRIBUTED] = e - s - covered
    return out


def reduce_spans(trace: dict, top: int = 10) -> dict | None:
    """``scopes``, ``idle_by_span`` and ``idle_gaps`` (module doc), with
    the megastep's executions and device seconds beside them. Device
    planes are averaged for idle time and summed for device time, as
    ``trace_reduce.reduce_trace`` does. ``scopes`` is None when no
    operation carries a ``mega.*`` scope, ``idle_by_span`` when no host
    line holds a ``sched.step`` span; None when the trace holds no
    device operation."""
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")
               and (_line(p, OPS_LINE) or _line(p, MODULES_LINE))]
    if not devices:
        return None
    hosts = [p for p in trace["planes"] if p["name"].startswith("/host:")]
    t0, t1 = traced_window({"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [e[:3] for e in ln["events"]]}
            for ln in p["lines"]]} for p in trace["planes"]]})
    spans = engine_line(trace)
    pieces = innermost(spans) if spans is not None else None
    starts = [pc[0] for pc in pieces or ()]
    scopes: dict[str, float] = {}
    idle: dict[str, float] = {}
    gaps = []
    mega_n, mega_s = 0, 0.0
    for p in devices:
        ops = _line(p, OPS_LINE) or []
        for e, own in zip(ops, self_times(ops)):
            sc = scope_of(e)
            if sc is not None:
                scopes[sc] = scopes.get(sc, 0.0) + own / 1e9
        for name, _, d in (e[:3] for e in _line(p, MODULES_LINE) or []):
            if MEGASTEP in module_name(name):
                mega_n += 1
                mega_s += d / 1e9
        for g in _gaps(ops or _line(p, MODULES_LINE), t0, t1):
            split = (_split(g, pieces, starts) if pieces is not None
                     else {UNATTRIBUTED: g[1] - g[0]})
            for k, v in split.items():
                idle[k] = idle.get(k, 0.0) + v / 1e9 / len(devices)
            gaps.append((g, max(split, key=split.get)))
    longest = sorted(gaps, key=lambda x: x[0][0] - x[0][1])[:top]
    return {
        "window_s": (t1 - t0) / 1e9,
        "megastep_count": mega_n,
        "megastep_device_s": mega_s,
        "scopes": dict(sorted(scopes.items())) if scopes else None,
        "idle_by_span": (dict(sorted(idle.items(), key=lambda kv: -kv[1]))
                         if pieces is not None else None),
        "idle_gaps": [[span, name_gap(hosts, s, e), (e - s) / 1e9]
                      for (s, e), span in longest],
    }


def summary(r: dict) -> dict:
    """What PERF.md records of a reduction: per-execution scope times
    (ms), their cover of the megastep's device time, and idle shares of
    the slice (%)."""
    out = {}
    n, scopes, idle = r["megastep_count"], r["scopes"], r["idle_by_span"]
    if scopes and n:
        out["scope_ms_per_megastep"] = {k: 1e3 * v / n
                                        for k, v in scopes.items()}
        out["scope_cover"] = sum(scopes.values()) / r["megastep_device_s"]
    if idle is not None and r["window_s"] > 0:
        share = {k: 100.0 * v / r["window_s"] for k, v in idle.items()}
        out["idle_share_by_span"] = share
        out["idle_admission_share"] = sum(
            share.get(k, 0.0)
            for k in ("sched.submit", "sched.prepare", "sched.admit"))
        out["idle_readback_share"] = sum(
            share.get(k, 0.0) for k in ("sched.readback",
                                        "metrics.readback"))
        total = sum(idle.values())
        out["idle_attributed"] = (1.0 - idle.get(UNATTRIBUTED, 0.0) / total
                                  if total else None)
    return out


if __name__ == "__main__":
    red = reduce_spans(load_xplane(sys.argv[1]))
    print(json.dumps({"reduced": red,
                      "summary": summary(red) if red else None}, indent=1))
