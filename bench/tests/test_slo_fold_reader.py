"""Self-checks of the reader of the ``slo_fold`` span (a tick's retired
records folded into the SLO tracker as one batch), on synthetic runs.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The reader gets a ``run`` with the stage totals a traced window would
carry (``run.stages``: stage -> (count, total seconds)) and must return
None, not raise, where the program has no such stage (as a program that
observes each record on its own has not).
"""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import run as bench_run  # noqa: E402

METRIC = "engine.slo_fold_us_per_decision"

# 10 ticks that retired, 5,000 decisions, a 2 s window
STAGES = {
    "tick": (11, 0.250),
    "retirement": (10, 0.100),
    "slo_fold": (10, 0.005),
    "gc": (40, 0.012),
}
# the stages a program without the batch fold records
OLD_STAGES = {k: v for k, v in STAGES.items() if k != "slo_fold"}


def _run(stages, decisions=5000):
    return SimpleNamespace(stages=stages, decisions=decisions,
                           window_s=2.0, trace=None)


@pytest.mark.parametrize("stages, decisions, want", [
    (STAGES, 5000, 0.005 / 5000 * 1e6),     # reads the stage's total
    (OLD_STAGES, 5000, None),               # silent without the stage
    (STAGES, 0, None),                      # silent on an empty window
    ({"gc": (0, 0.0)}, 0, None),
])
def test_slo_fold_reader(stages, decisions, want):
    got = bench_run.reader(METRIC)(_run(stages, decisions))
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want, rel=1e-12)


def test_slo_fold_metric_declared_for_the_cell():
    cell = bench_run.load_cell("sar_ideal_backlog")
    assert METRIC in {m["name"] for m in cell.per_layer}
