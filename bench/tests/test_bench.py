"""Self-checks of the benchmark, on the CPU at its own small sizes.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They cover the trace reduction (on a small trace recorded on a TPU v5e),
the operation and byte counts, the committed weights against their
recipe, the comparison that decides ``correct`` (a clean run correct;
the precision control, a round whose state is dropped, an altered
answer and a GRNG other than the configuration's not correct), and the
refusal to run without a TPU.  The CPU runs hold 64 slots, not the
configuration's 2,048.
"""

from __future__ import annotations

import copy
import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from bench import readout, sard, trace  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.systems import sar as bench_system  # noqa: E402

DATA = Path(__file__).parent / "data"


# ----------------------------------------------------------------------
# counts
# ----------------------------------------------------------------------
def test_trunk_flops_match_the_three_convs():
    layers = readout.trunk_layers(32, (16, 32, 64), 3)
    assert layers == [(225, 9, 16), (49, 144, 32), (9, 288, 64)]
    assert readout.trunk_flops(32, (16, 32, 64), 3) == (
        64_800 + 451_584 + 331_776)


def test_decision_kernel_cost_tiny():
    # B=2 slots, N=1 class, R=1 sample: y_mu, x_sigma 4; m 32; sel 32;
    # mask 2; sums out 8
    flops, byts = readout.decision_kernel_cost(2, 1, 1)
    assert flops == 2 * 2 * 16 + 8 * 2
    assert byts == 4 * 78


def test_head_flops_tiny():
    # d_in 2, N 1: y_mu, x_sigma and 16 basis products of 2x1
    assert readout.head_flops(2, 1) == 2 * 2 * 1 * 18


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    share, bound = readout.roofline_share(10, 1e3, 1e3, 1e-4, peaks)
    assert bound == "memory" and share == pytest.approx(10.0)
    assert readout.roofline_share(0, 1, 1, 1.0, peaks) is None


# ----------------------------------------------------------------------
# trace reduction
# ----------------------------------------------------------------------
def _synthetic():
    ms = 1e6
    host = [("main", trace.WINDOW_SPAN, 0.0, 10 * ms),
            ("main", "retire", 2 * ms, 3 * ms),
            ("main", "admit", 6 * ms, 1 * ms),
            ("runtime", "transfer", 2 * ms, 3.5 * ms)]
    device = [(0, trace.OPS_LINE, "fusion", 1 * ms, 1 * ms),
              (0, trace.OPS_LINE, "decision_stats_pallas.1", 5 * ms, 1 * ms),
              (0, trace.OPS_LINE, "fusion", 5.5 * ms, 1 * ms),
              (0, trace.MODULES_LINE, "jit_featurize", 5 * ms, 2 * ms),
              (1, trace.OPS_LINE, "fusion", 9 * ms, 2 * ms)]
    return {"host": host, "device": device}


def test_reduce_unions_clips_and_names_gaps():
    red = trace.reduce(_synthetic())
    assert red["window_s"] == pytest.approx(0.010)
    d0 = red["devices"][0]
    assert d0["busy_s"] == pytest.approx(0.0025)      # [1,2] u [5,6.5]
    assert red["devices"][1]["busy_s"] == pytest.approx(0.001)  # clipped
    assert red["busiest"] == 0
    # idle on device 0: [0,1] and [6.5,10] with nothing open on the
    # driving thread at their middles, [2,5] inside "retire"
    assert red["breakdown"]["idle_gaps"] == [
        ["idle host", pytest.approx(0.0045)], ["retire", pytest.approx(0.003)]]
    assert trace.device_time(red, "featurize", "modules") == (
        1, pytest.approx(0.002))
    assert trace.device_time(red, "decision_stats_pallas") == (
        1, pytest.approx(0.001))


def test_reduce_refuses_a_trace_without_device_work():
    ev = _synthetic()
    ev["device"] = []
    with pytest.raises(RuntimeError):
        trace.reduce(ev)


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob(
    "trace_small_*.json")))
def test_reduce_recorded_tpu_trace(name):
    """A short window recorded on a TPU v5e: the engine's programs and
    kernels are found by the names the readers look for."""
    ev = json.loads((DATA / name).read_text())
    red = trace.reduce(ev)
    assert 0 < red["busy_s"] < red["window_s"]
    calls, secs = trace.device_time(red, "featurize", "modules")
    assert calls > 0 and secs > 0
    calls, secs = trace.device_time(red, "decision_stats_pallas")
    assert calls > 0 and secs > 0
    assert len(red["breakdown"]["device_ops"]) <= 10
    assert len(red["breakdown"]["idle_gaps"]) <= 10


# ----------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------
@pytest.mark.parametrize("config", sorted(
    p.stem for p in (ROOT / "bench" / "configs").glob("*.json")
    if json.loads(p.read_text()).get("system") == "sar"))
def test_weights_are_remade_from_their_recipe(config, tmp_path):
    """Each SAR configuration's weights file is what its recipe trains on
    the CPU, and a run refuses a file trained from another recipe."""
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    committed = sard.WEIGHTS / f"{config}.npz"
    assert sard.main([config, "--out", str(tmp_path / "w.npz")]) == 0
    recipe = sard.recipe_of(cfg)
    fresh = sard.load_params(tmp_path / "w.npz", recipe)
    kept = sard.load_params(committed, recipe)
    for a, b in zip(*(jax_leaves(t) for t in (fresh, kept))):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)
    other = copy.deepcopy(cfg)
    other["model"]["grng"]["seed"] += 1
    with pytest.raises(ValueError):
        sard.load_params(committed, sard.recipe_of(other))


def jax_leaves(tree):
    import jax
    return jax.tree.leaves(tree)


# ----------------------------------------------------------------------
# correctness: a clean run passes, the control and each fault do not
# ----------------------------------------------------------------------
CPU_SLOTS = 64


def _cpu_cell(name):
    cell = bench_run.load_cell(name)
    # the CPU computes float32 contractions in full, so the reference
    # follows it there; on a TPU the configuration's rounding holds
    cell.cfg["precision"]["reference_dot"] = "f32"
    cell.cfg["slots"] = CPU_SLOTS
    return cell


def _run(name, seed, **kw):
    return bench_run.run_cell(_cpu_cell(name), seed, 1.0, False,
                              require_tpu=False,
                              log=lambda *a, **k: None, **kw)


def test_clean_run_is_correct():
    res = _run("sar_ideal_backlog", 2**31 + 17)
    assert res["correct"], res["checks"]
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["metrics"]["decisions_per_s"]["value"] > 0


def test_precision_control_is_not_correct():
    res = _run("sar_ideal_backlog", 2**31 + 18, control="reference_bf16")
    assert not res["correct"], res["checks"]


def test_altered_answer_is_not_correct(monkeypatch):
    """An answer altered where it is produced: every 5th retired verdict
    is swapped between accept and flag."""
    from repro.serving import engine
    orig = engine._EngineBase._retire

    def retire(self, slot_idx, verdict, fin, extra_samples, verdict_s=0.0):
        req = self.slots[slot_idx].req
        if req.rid % 5 == 0 and verdict != engine.ESCALATE:
            verdict = engine.FLAG if verdict == engine.ACCEPT else \
                engine.ACCEPT
        return orig(self, slot_idx, verdict, fin, extra_samples,
                    verdict_s=verdict_s)

    monkeypatch.setattr(engine._EngineBase, "_retire", retire)
    res = _run("sar_ideal_backlog", 2**31 + 19)
    assert not res["correct"], res["checks"]


def test_altered_confidence_is_not_correct(monkeypatch):
    """The served confidence moved by 1e-2 where it is produced."""
    from repro.serving import metrics
    orig = metrics.ServingMetrics.record

    def record(self, rec):
        rec.confidence += 1e-2
        return orig(self, rec)

    monkeypatch.setattr(metrics.ServingMetrics, "record", record)
    res = _run("sar_ideal_backlog", 2**31 + 20)
    assert not res["correct"], res["checks"]


def test_round_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    """The escalation round hands back the statistics it was given, so a
    request that escalates restarts its sums every tick."""
    import jax
    import jax.numpy as jnp
    from repro.serving import engine
    orig = engine._sar_round_fn

    def round_fn(*a, **k):
        fn = orig(*a, **k)

        def stale(pool, stats, *rest):
            kept = jax.tree.map(jnp.copy, stats)   # the round donates it
            return (kept,) + tuple(fn(pool, stats, *rest)[1:])
        return stale

    monkeypatch.setattr(engine, "_sar_round_fn", round_fn)
    res = _run("sar_ideal_backlog", 2**31 + 21)
    assert not res["correct"], res["checks"]


def test_grng_other_than_the_configuration_is_not_correct(monkeypatch):
    """The program serves the configuration's GRNG: one whose seed
    differs on the program's side only is caught."""
    real = bench_system.program_config

    def other(cfg):
        cfg = copy.deepcopy(cfg)
        cfg["model"]["grng"]["seed"] += 1
        return real(cfg)

    monkeypatch.setattr(bench_system, "program_config", other)
    res = _run("sar_ideal_backlog", 2**31 + 22)
    assert not res["correct"], res["checks"]


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "sar_ideal_backlog", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
