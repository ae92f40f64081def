"""Host milliseconds the scheduler spent per submitted query on its
candidate filtering and matching order over the window, on the engine
thread while the device may wait: delta ``host_prepare_time_s`` / delta
``prepared``, host clock, from ``/metrics``."""


def read(ctx):
    b, a = ctx["before"], ctx["after"]
    if "prepared" not in a or "prepared" not in b:
        return None
    n = a["prepared"] - b["prepared"]
    if n <= 0:
        return None
    return 1e3 * (a["host_prepare_time_s"] - b["host_prepare_time_s"]) / n
