"""Verdicts retired in the window over the window's length (host clock)."""


def read(run):
    return run.decisions / run.window_s
