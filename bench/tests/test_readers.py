"""Self-checks of the readers of the tick's stage spans and of the idle
gaps no span covers, on synthetic runs.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each reader gets a ``run`` with the stage totals a traced window would
carry (``run.stages``: stage -> (count, total seconds)) or a trace
breakdown, and must return None, not raise, where the program has no
such stage (as a program without the tick's spans has not).
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run as bench_run  # noqa: E402

# 10 ticks that pulled verdicts (one more, idle), 5,000 decisions, a 2 s
# window
STAGES = {
    "tick": (11, 0.250),
    "admission": (10, 0.070),
    "admit_stack": (10, 0.020),
    "featurize": (10, 0.030),
    "admit_enqueue": (10, 0.015),
    "slot_mask": (10, 0.004),
    "dispatch": (10, 0.006),
    "triage_loop": (10, 0.060),
    "round_wait": (10, 0.004),
    "verdict_pull": (110, 0.055),
    "retirement": (10, 0.100),
    "gc": (40, 0.012),
    "gc_full": (1, 0.004),
}
# the stages a program without the tick's spans records
OLD_STAGES = {k: STAGES[k] for k in
              ("admission", "featurize", "dispatch", "triage_loop",
               "retirement")}

SPAN_READERS = {
    "engine.round_wait_us_per_tick": 0.004 / 10 * 1e6,
    "engine.verdict_pull_us_per_tick": 0.055 / 10 * 1e6,
    "engine.d2h_pulls_per_tick": 11.0,
    "engine.admit_stack_us_per_decision": 0.020 / 5000 * 1e6,
    "engine.admit_enqueue_us_per_decision": 0.015 / 5000 * 1e6,
    "engine.tick_self_us_per_tick": (0.250 - 0.240) / 11 * 1e6,
    "host.gc_pause_ms_per_s": 0.012 * 1e3 / 2.0,
}


def _run(stages, trace=None):
    return SimpleNamespace(stages=stages, decisions=5000, window_s=2.0,
                           trace=trace)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_reads_the_stage_totals(metric):
    got = bench_run.reader(metric)(_run(STAGES))
    assert got == pytest.approx(SPAN_READERS[metric], rel=1e-12)


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_is_silent_without_its_stages(metric):
    assert bench_run.reader(metric)(_run(OLD_STAGES)) is None


def test_span_readers_are_silent_on_an_empty_window():
    empty = SimpleNamespace(stages={"gc": (0, 0.0)}, decisions=0,
                            window_s=2.0, trace=None)
    for metric in SPAN_READERS:
        got = bench_run.reader(metric)(empty)
        assert got is None or got == 0.0, metric


def _breakdown(gaps):
    return {"window_s": 2.0, "breakdown": {"idle_gaps": gaps,
                                           "device_ops": []}}


@pytest.mark.parametrize("gaps, share", [
    ([["idle host", 1.5], ["retirement", 0.3]], 75.0),
    ([["retirement", 0.9], ["admission", 0.5], ["verdict_pull", 0.1]],
     5.0),                                  # absent: the smallest listed
    ([], 0.0),                              # no idle gap at all
])
def test_idle_unspanned_share(gaps, share):
    read = bench_run.reader("device.idle_unspanned_share")
    assert read(_run(STAGES, _breakdown(gaps))) == pytest.approx(share)


def test_idle_unspanned_share_untraced():
    read = bench_run.reader("device.idle_unspanned_share")
    assert read(_run(STAGES)) is None


def test_new_metrics_declared_for_the_cell():
    cell = bench_run.load_cell("sar_ideal_backlog")
    names = {m["name"] for m in cell.per_layer}
    assert set(SPAN_READERS) | {"device.idle_unspanned_share"} <= names
