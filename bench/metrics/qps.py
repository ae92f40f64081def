"""Requests answered ``ok``/``limit`` inside the window, per second of
the window (client's clock)."""


def read(ctx):
    return ctx["requests"]["answered_in_window"] / ctx["window_s"]
