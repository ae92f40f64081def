"""Reduce a profiler trace to device busy time, time per program and
the breakdown of where the device's time and idle gaps go.

    python bench/trace_reduce.py <file.xplane.pb>     # prints the reduction

:func:`load_xplane` turns the profiler's ``.xplane.pb`` into plain data
(planes, their lines, events as ``[name, start_ns, duration_ns]``);
:func:`reduce_trace` works on that data alone, so a small recorded
trace kept as JSON checks it. Device planes are those named
``/device:...``; on a TPU their ``XLA Ops`` line holds one event per
operation and their ``XLA Modules`` line one per program execution
(``jit_<name>(<id>)``). Host planes (``/host:...``) hold the host's
threads, whose events name what the host was doing in a device gap.
"""
from __future__ import annotations

import json
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            ev = [[e.name, int(e.start_ns), int(e.duration_ns)]
                  for e in ln.events]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def module_name(event_name: str) -> str:
    """``jit_run_device_megastep(123)`` -> ``jit_run_device_megastep``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.12 = s32[8]{0} fusion(...)`` -> ``fusion.12``: a TPU
    trace names an operation by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


# host calls that block: a thread inside one of these is waiting, not
# working, so it does not name a device gap
WAITING = re.compile(r"(\bwait\b|acquire|\bget$|select|poll|sleep|"
                     r"setprofile|recv|accept|readline|\bjoin\b)")


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _line(plane: dict, name: str) -> list | None:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return None


def traced_window(trace: dict) -> tuple[int, int]:
    """From the end of the host's ``start_trace`` call to the start of
    its ``stop_trace`` call where the trace holds both, else the extent
    of every event."""
    start = stop = None
    every = []
    for p in trace["planes"]:
        for ln in p["lines"]:
            for n, s, d in ln["events"]:
                every.append((s, s + d))
                if p["name"].startswith("/host:"):
                    if n.endswith("start_trace"):
                        start = s + d
                    elif n.endswith("stop_trace"):
                        stop = s
    if start is not None and stop is not None and stop > start:
        return start, stop
    return min(s for s, _ in every), max(e for _, e in every)


def name_gap(hosts: list[dict], s: int, e: int) -> str:
    """What the host was doing in the device gap ``[s, e)``: on each host
    thread the calls that contain the gap's middle, outermost first; the
    thread whose innermost such call is not a wait and overlaps the gap
    most names it, by its last three calls."""
    mid = (s + e) // 2
    best, over = "no host event", 0
    for p in hosts:
        for ln in p["lines"]:
            chain = sorted((ev for ev in ln["events"]
                            if ev[1] <= mid < ev[1] + ev[2]),
                           key=lambda ev: (ev[1], -ev[2]))
            if not chain or WAITING.search(chain[-1][0]):
                continue
            inner = chain[-1]
            o = min(e, inner[1] + inner[2]) - max(s, inner[1])
            if o > over:
                best = " > ".join(ev[0] for ev in chain[-3:])
                over = o
    return best


def reduce_trace(trace: dict, top: int = 10) -> dict | None:
    """Busy and window seconds (busy averaged over the device planes),
    per-program execution counts and device seconds, the ``top``
    operations by device time, and the ``top`` longest idle gaps, each
    named by what the host was doing (:func:`name_gap`). None when the
    trace holds no device operation."""
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")
               and (_line(p, OPS_LINE) or _line(p, MODULES_LINE))]
    if not devices:
        return None
    hosts = [p for p in trace["planes"] if p["name"].startswith("/host:")]
    t0, t1 = traced_window(trace)
    busy_ns, modules, ops, gaps = [], {}, {}, []
    for p in devices:
        op_ev = _line(p, OPS_LINE) or _line(p, MODULES_LINE)
        merged = [(max(s, t0), min(e, t1)) for s, e in
                  _union([(s, s + d) for _, s, d in op_ev])
                  if e > t0 and s < t1]
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, _, d in op_ev:
            k = op_name(name)
            ops[k] = ops.get(k, 0) + d
        for name, _, d in _line(p, MODULES_LINE) or []:
            m = modules.setdefault(module_name(name), [0, 0])
            m[0] += 1
            m[1] += d
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "n_devices": len(devices),
        "modules": {k: {"count": c, "device_s": d / 1e9}
                    for k, (c, d) in modules.items()},
        "device_ops": [[n, d / 1e9] for n, d in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name_gap(hosts, s, e), (e - s) / 1e9]
                      for s, e in longest],
    }


if __name__ == "__main__":
    print(json.dumps(reduce_trace(load_xplane(sys.argv[1])), indent=1))
