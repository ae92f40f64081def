"""Share of the scheduler's wave capacity that carried rows over the
window, by the scheduler's own measure (``WaveScheduler``: rows expanded
per dispatch over ``wave_size`` x the dispatch's megastep iterations):
the change of ``mean_occupancy`` x ``waves`` over the change of
``waves``, from ``/metrics``."""


def read(ctx):
    b, a = ctx["before"], ctx["after"]
    if "waves" not in a or "waves" not in b:
        return None
    waves = a["waves"] - b["waves"]
    if waves <= 0:
        return None
    occ = a["mean_occupancy"] * a["waves"] - b["mean_occupancy"] * b["waves"]
    return 100.0 * occ / waves
