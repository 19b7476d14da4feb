"""Host time in the engine's ``admission`` stage (featurize enqueue,
scatter, stats reset) per decision retired in the window: the
StageProfiler's host-clock total."""


def read(run):
    _, total = run.stages.get("admission", (0, 0.0))
    return total / run.decisions * 1e6 if run.decisions else None
