"""Host time in the ``tick`` stage that none of its five direct
children (admission, slot_mask, dispatch, triage_loop, retirement)
covers, per tick (StageProfiler): what the tick tree leaves unnamed.
None where the program has no ``tick`` stage."""

CHILDREN = ("admission", "slot_mask", "dispatch", "triage_loop",
            "retirement")


def read(run):
    ticks, total = run.stages.get("tick", (0, 0.0))
    if not ticks:
        return None
    inner = sum(run.stages.get(s, (0, 0.0))[1] for s in CHILDREN)
    return (total - inner) / ticks * 1e6
