"""Host milliseconds the scheduler spent per wave over the window,
dispatching and on its own bookkeeping (not blocked on the device):
delta (``dispatch_time_s`` + ``host_time_s``) / delta ``waves``, host
clock, from ``/metrics``."""


def read(ctx):
    b, a = ctx["before"], ctx["after"]
    if "waves" not in a or "waves" not in b:
        return None
    waves = a["waves"] - b["waves"]
    if waves <= 0:
        return None
    host = (a["dispatch_time_s"] - b["dispatch_time_s"]
            + a["host_time_s"] - b["host_time_s"])
    return 1e3 * host / waves
