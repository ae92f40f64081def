"""Mean milliseconds a request waited in the server's queues over the
window, from its acceptance to its query taking an engine slot: delta
``queue_wait_s`` / delta ``queue_waits``, host clock, from ``/metrics``
(the engine report, where the server notes each wait)."""


def read(ctx):
    b, a = ctx["before"], ctx["after"]
    if "queue_waits" not in a or "queue_waits" not in b:
        return None
    n = a["queue_waits"] - b["queue_waits"]
    if n <= 0:
        return None
    return 1e3 * (a["queue_wait_s"] - b["queue_wait_s"]) / n
