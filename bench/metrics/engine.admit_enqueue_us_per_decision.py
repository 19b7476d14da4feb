"""Host time in the ``admit_enqueue`` stage (slot assignment, the slot
index upload, the enqueued scatter and stats reset: the last part of
``admission``) per decision retired in the window (StageProfiler).  None
where the program has no such stage."""


def read(run):
    if not run.decisions or "admit_enqueue" not in run.stages:
        return None
    return run.stages["admit_enqueue"][1] / run.decisions * 1e6
