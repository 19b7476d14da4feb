"""Host time in the engine's ``retirement`` stage (``_retire_decided``)
per decision retired in the window (StageProfiler total)."""


def read(run):
    _, total = run.stages.get("retirement", (0, 0.0))
    return total / run.decisions * 1e6 if run.decisions else None
