"""Share of the traced window in which the busiest chip was idle while
no span was open on the driving thread: the idle gaps that
``bench/trace.py`` labels "idle host", over the window.  The breakdown
lists only the largest labels; when "idle host" is not among them, the
smallest listed gap is reported, an upper bound."""

UNSPANNED = "idle host"        # bench/trace.py's label for such a gap


def read(run):
    if run.trace is None:
        return None
    gaps = dict(run.trace["breakdown"]["idle_gaps"])
    secs = gaps.get(UNSPANNED, min(gaps.values(), default=0.0))
    return 100.0 * secs / run.trace["window_s"]
