"""Share of the search's row outcomes over the window that were dead-end
prunes by the pattern store: delta ``deadend_prunes`` / (delta
``deadend_prunes`` + delta ``rows_created``), from ``/metrics``."""


def read(ctx):
    b, a = ctx["before"], ctx["after"]
    if "deadend_prunes" not in a or "deadend_prunes" not in b:
        return None
    pr = a["deadend_prunes"] - b["deadend_prunes"]
    rows = a["rows_created"] - b["rows_created"]
    if pr + rows <= 0:
        return None
    return 100.0 * pr / (pr + rows)
