"""Seconds from the process's start to the first request of the window:
JAX start-up, data and query generation, server construction, warm-up
(compiling or loading every program), warm-up requests over the wire
and the traffic's own ``warmup_s`` before the window."""


def read(ctx):
    return ctx["setup_s"]
