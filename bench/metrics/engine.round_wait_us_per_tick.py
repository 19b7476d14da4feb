"""Host time in the ``round_wait`` stage (blocking until the round
dispatch's outputs are ready, the first part of ``triage_loop``) per
tick that pulled verdicts (StageProfiler).  None where the program has
no such stage."""


def read(run):
    ticks, _ = run.stages.get("triage_loop", (0, 0.0))
    if not ticks or "round_wait" not in run.stages:
        return None
    return run.stages["round_wait"][1] / ticks * 1e6
