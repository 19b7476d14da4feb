"""Device time of the featurize program (the conv trunk and the
activation basis) per decision retired in the traced window."""

from bench.trace import device_time

PROGRAM = "featurize"        # jit name of the engine's featurize program


def read(run):
    if run.trace is None or not run.decisions:
        return None
    calls, secs = device_time(run.trace, PROGRAM, line="modules")
    return secs / run.decisions * 1e6 if calls else None
