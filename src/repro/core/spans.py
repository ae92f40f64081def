"""Host spans and device scopes: the one tracing mechanism of the engine.

``with span(name, acc, **ids):`` is a ``jax.profiler.TraceAnnotation``
named ``name`` with arguments ``ids`` (``query_id=`` for spans of one
request), so a profiler trace shows what the host thread was doing while
the device ran or waited. Given ``acc``, an :class:`Acc` or a tuple of
them, the span also adds its host-clock duration to those accumulators:
the scheduler's host/device time split in ``scheduler_stats()`` is the
sum of its spans, not a second set of timers beside them.

With the profiler off an annotation costs about a microsecond; a wave
opens a handful of spans.

The device programs name their phases with ``jax.named_scope`` and the
``MEGA_*`` names below: a scope lands in the ``op_name`` metadata of
each HLO instruction traced inside it (the profiler's ``tf_op`` of the
operation) and changes nothing else.
"""
from __future__ import annotations

import functools
import time

from jax.profiler import TraceAnnotation

# ---- host spans: engine thread of the server -------------------------
SERVER_ADMIT_READY = "server.admit_ready"  # wire requests into the scheduler
SERVER_DELIVER = "server.deliver"          # embeddings out to the wire
SERVER_REPORT = "server.report"            # /metrics and /slo snapshot
SERVER_WAIT = "server.wait"                # nothing to do: wait for work
METRICS_READBACK = "metrics.readback"      # device reads of the snapshot

# ---- host spans: scheduler -------------------------------------------
SCHED_STEP = "sched.step"                  # one scheduling step
SCHED_SUBMIT = "sched.submit"              # one query into the queue
SCHED_PREPARE = "sched.prepare"            # its candidates and order
SCHED_ADMIT = "sched.admit"                # queued queries into slots
SCHED_DISPATCH_DEVICE = "sched.dispatch_device"  # device-stack dispatch
SCHED_DISPATCH_WAVE = "sched.dispatch_wave"      # pack + wave dispatch
SCHED_RETIRE_DEVICE = "sched.retire_device"      # fold a device digest
SCHED_RETIRE_WAVE = "sched.retire_wave"          # fold a wave digest
SCHED_READBACK = "sched.readback"          # blocking device -> host read
SCHED_DIGEST = "sched.digest"              # host fold of what was read
SCHED_FINISH = "sched.finish"              # one query retires
SCHED_EXPORT = "sched.export"              # wedged device stack to host
STORE_FLUSH = "store.flush"                # Δ pattern batch to the device

# ---- device scopes: phases of run_device_megastep --------------------
MEGA_ROOTS = "mega.roots"        # root rows into free stack entries
MEGA_SELECT = "mega.select"      # loop control and wave selection
MEGA_REFINE = "mega.refine"      # Eq. 2 refinement (every backend)
MEGA_INJECT = "mega.inject"      # injectivity masks
MEGA_EXTRACT = "mega.extract"    # top-kpr children and embeddings
MEGA_PROBE = "mega.probe"        # Eq. 7 dead-end lookup in Δ
MEGA_ALLOC = "mega.alloc"        # children into entries, stack updates
MEGA_STORE = "mega.store"        # in-loop Lemma-1 pattern stores
MEGA_RESOLVE = "mega.resolve"    # in-loop Lemma-4 resolution sweep
MEGA_DRAIN = "mega.drain"        # final sweeps and the digest


class Acc:
    """Host-clock seconds summed over the spans given this accumulator,
    less what the accumulators in ``less`` gained inside those spans: a
    bucket that leaves out the buckets nested in it."""

    __slots__ = ("s", "less")

    def __init__(self, *less: "Acc"):
        self.s = 0.0
        self.less = less


class span:
    """A profiler annotation that also feeds accumulators (module doc).
    ``t0`` and ``t1`` hold the host clock (``time.perf_counter``) at its
    entry and exit: the interval its accumulators summed."""

    __slots__ = ("name", "ids", "t0", "t1", "_ann", "_acc", "_nested")

    def __init__(self, name: str, acc: Acc | tuple | None = None, **ids):
        self.name = name
        self.ids = ids
        self._ann = TraceAnnotation(name, **ids)
        self._acc = (acc if isinstance(acc, tuple)
                     else () if acc is None else (acc,))

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._nested = [[b.s for b in a.less] for a in self._acc]
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        dt = self.t1 - self.t0
        for a, before in zip(self._acc, self._nested):
            a.s += max(0.0, dt - sum(b.s - s0
                                     for b, s0 in zip(a.less, before)))
        self._ann.__exit__(*exc)
        return False


def traced(name: str):
    """Decorator: run the method inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
