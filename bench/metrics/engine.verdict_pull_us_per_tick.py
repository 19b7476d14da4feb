"""Host time in the ``verdict_pull`` spans (copying the round's results
to the host, one span per array, the rest of ``triage_loop``) per tick
that pulled verdicts (StageProfiler).  None where the program has no
such stage."""


def read(run):
    ticks, _ = run.stages.get("triage_loop", (0, 0.0))
    if not ticks or "verdict_pull" not in run.stages:
        return None
    return run.stages["verdict_pull"][1] / ticks * 1e6
