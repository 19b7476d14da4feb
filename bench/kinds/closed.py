"""Closed loop: the admission queue is topped up before every tick.

Parameters (traffic file): ``queue_per_slot`` — requests kept waiting
per slot of the system, so every slot freed by a retirement refills on
the next tick; ``warmup_ticks`` — ticks run during set-up.
"""

from __future__ import annotations

import time


def _top_up(system, feed, depth: int) -> None:
    while system.pending < depth:
        system.submit(feed.make())


def warmup(system, feed, spec: dict) -> None:
    depth = int(spec["queue_per_slot"] * system.slots)
    for _ in range(int(spec.get("warmup_ticks", 16))):
        _top_up(system, feed, depth)
        system.tick()


def window(system, feed, spec: dict, seconds: float, seed: int) -> dict:
    """Tick for ``seconds``; every retirement in the window counts, and
    every request submitted in it is due (timed from its submission)."""
    depth = int(spec["queue_per_slot"] * system.slots)
    due = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    ticks = 0
    while time.perf_counter() < t_end:
        first = feed.next_rid
        _top_up(system, feed, depth)
        now = time.perf_counter()
        due.extend((rid, now) for rid in range(first, feed.next_rid))
        system.tick()
        ticks += 1
    return {"t0": t0, "t1": time.perf_counter(), "ticks": ticks,
            "due": due}
