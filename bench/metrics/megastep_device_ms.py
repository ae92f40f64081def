"""Mean device milliseconds per execution of the ``run_device_megastep``
program in the traced slice (the trace's ``XLA Modules`` line)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    runs = [m for name, m in tr["modules"].items()
            if "run_device_megastep" in name]
    n = sum(m["count"] for m in runs)
    if n == 0:
        return None
    return 1e3 * sum(m["device_s"] for m in runs) / n
