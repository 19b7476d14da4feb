"""Host time in the ``triage_loop`` stage (waiting for and pulling the
on-device escalation loop's verdicts) per tick (StageProfiler)."""


def read(run):
    count, total = run.stages.get("triage_loop", (0, 0.0))
    return total / count * 1e6 if count else None
