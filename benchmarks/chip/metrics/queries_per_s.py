"""Queries completed in the window over the window's seconds (host clock).

The window ends when the last query started before the deadline
finishes, so every query counted is whole and all of its time counts."""


def read(run):
    return run.n / run.window_s if run.window_s > 0 else None
