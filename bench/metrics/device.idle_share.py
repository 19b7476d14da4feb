"""Share of the traced window in which no operation ran on the busiest
chip (1 - union of its op intervals / window)."""


def read(run):
    if run.trace is None:
        return None
    busiest = run.trace["devices"][run.trace["busiest"]]
    return 100.0 * (1.0 - busiest["busy_s"] / run.trace["window_s"])
