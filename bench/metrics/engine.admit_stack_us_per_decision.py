"""Host time in the ``admit_stack`` stage (queue pops, stacking the
crops, padding to the pool's size: the first part of ``admission``) per
decision retired in the window (StageProfiler).  None where the program
has no such stage."""


def read(run):
    if not run.decisions or "admit_stack" not in run.stages:
        return None
    return run.stages["admit_stack"][1] / run.decisions * 1e6
