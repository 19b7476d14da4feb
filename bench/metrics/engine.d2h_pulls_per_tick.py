"""Device-to-host copies per tick that pulled verdicts: the count of
``verdict_pull`` spans (one per array copied) over the count of
``triage_loop`` spans (StageProfiler).  None where the program has no
such stage."""


def read(run):
    ticks, _ = run.stages.get("triage_loop", (0, 0.0))
    if not ticks or "verdict_pull" not in run.stages:
        return None
    return run.stages["verdict_pull"][0] / ticks
