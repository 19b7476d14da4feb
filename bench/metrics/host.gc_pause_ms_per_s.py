"""Python garbage-collector pauses per second of the window: the ``gc``
stage's total (every collection, timed by the program's collector hook)
over the window (StageProfiler).  None where the program records no
collections."""


def read(run):
    if "gc" not in run.stages:
        return None
    return run.stages["gc"][1] * 1e3 / run.window_s
