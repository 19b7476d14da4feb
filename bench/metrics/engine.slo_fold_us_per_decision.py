"""Host time in the ``slo_fold`` stage (one tick's retired records
folded into the SLO tracker as one batch, inside ``retirement``) per
decision retired in the window (StageProfiler).  None where the program
has no such stage."""


def read(run):
    if not run.decisions or "slo_fold" not in run.stages:
        return None
    return run.stages["slo_fold"][1] / run.decisions * 1e6
