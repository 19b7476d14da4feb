"""Performance-observability layer: stage profiler primitives, bench
history records, the regression gate, the decision-path roofline, and
the compile-cache invariant.

The load-bearing assertions mirror the PR's acceptance criteria:
  * regress.compare passes on identical metrics and FAILS on an
    injected 2x slowdown in a wall-clock metric;
  * every history record is schema-versioned and carries git SHA +
    backend fingerprint (with the honest interpret_mode bit);
  * constructing a second engine with identical frozen configs
    triggers ZERO new builder compilations (process-wide lru_cache);
  * Prometheus text-format edge cases round-trip: label escaping,
    NaN/inf histogram counts, empty histograms, overflow bucket.
"""

from __future__ import annotations

import json
import math

import jax
import numpy as np
import pytest

from repro.obs import prof
from repro.obs.prof import StageProfiler, NULL_PROFILER
from repro.obs.registry import MetricsRegistry, serving_registry

from benchmarks import history, regress


# ----------------------------------------------------------------------
# stage profiler primitives
# ----------------------------------------------------------------------
def test_stage_profiler_observe_and_snapshot():
    p = StageProfiler()
    p.observe("dispatch", 1e-4)
    p.observe("dispatch", 2e-4)
    p.observe("dispatch", float("nan"))      # dropped
    p.observe("dispatch", -1.0)              # clamped to 0
    p.observe("dispatch", 1e9)               # beyond last edge: overflow
    with p.span("triage_loop"):
        pass
    snap = p.snapshot()
    d = snap["dispatch"]
    assert d["count"] == 4                   # nan dropped
    assert d["overflow"] == 1
    assert sum(d["counts"]) == 3
    # overflow observations are finite: they still count toward total_s
    assert d["total_s"] == pytest.approx(1e9 + 3e-4)
    assert math.isfinite(d["mean_s"])
    assert snap["triage_loop"]["count"] == 1
    # serving stages come first, in order, in the snapshot
    keys = list(snap)
    assert keys[: keys.index("triage_loop") + 1] == \
        [s for s in prof.SERVING_STAGES
         if s in keys][: keys.index("triage_loop") + 1]


def test_null_profiler_is_inert():
    assert NULL_PROFILER.enabled is False
    NULL_PROFILER.observe("x", 1.0)
    with NULL_PROFILER.span("x"):
        pass
    assert NULL_PROFILER.snapshot() == {}


def test_compile_counters_shape():
    cc = prof.compile_counters()
    assert set(cc) == {"builder_builds", "xla_compile_events",
                       "xla_compile_seconds"}
    assert isinstance(cc["builder_builds"], dict)


def test_compiled_cost_of_simple_fn():
    f = jax.jit(lambda a, b: a @ b)
    x = jax.ShapeDtypeStruct((8, 16), jax.numpy.float32)
    y = jax.ShapeDtypeStruct((16, 4), jax.numpy.float32)
    rec = prof.compiled_cost("mm", f, x, y)
    assert rec["name"] == "mm"
    assert rec["flops"] >= 2 * 8 * 16 * 4 * 0.5   # loop-aware estimate
    assert rec["hbm_bytes"] > 0
    assert rec["compile_s"] > 0


def test_trace_capture_none_is_noop():
    with prof.trace_capture(None):
        pass


def test_trace_capture_raises_when_profiler_cannot_start(monkeypatch,
                                                        tmp_path):
    def refuse(log_dir):
        raise RuntimeError("profiler busy")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler busy"):
        with prof.trace_capture(str(tmp_path)):
            pass


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_enable_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the
    cache is one fixed directory in the checkout, whatever the cwd.
    Either way every compile is cached, however short."""
    from pathlib import Path

    from repro.launch.compile_cache import enable_compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.chdir(tmp_path)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)
        want = env_dir
    try:
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)


# ----------------------------------------------------------------------
# compile-cache invariant (satellite: compilation caching regression)
# ----------------------------------------------------------------------
def test_engine_compile_cache_shared_across_instances():
    """Two engines with identical frozen configs: the first builds each
    jitted builder at most once; the second builds NOTHING (the
    process-wide lru_cache is the compile cache, and the new
    compile-event counter is how we now catch cache-key drift)."""
    from repro.launch.serve import make_sar_stream
    from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
    from repro.serving import SarServingEngine, TriagePolicy

    cfg = SarCnnConfig()
    params = init_sar_cnn(jax.random.PRNGKey(11), cfg)
    # unique thresholds -> guaranteed-cold lru_cache keys for this test
    policy = TriagePolicy(conf_threshold=0.7123, mi_threshold=0.0511,
                          r_min=4, r_max=20)

    def run_one():
        before = dict(prof.builder_builds())
        eng = SarServingEngine(params, cfg, n_slots=8, policy=policy,
                               adaptive_mode=True, fused=True,
                               telemetry=False)
        for r in make_sar_stream(8, corrupt_frac=0.0):
            eng.submit(r)
        eng.run()
        after = prof.builder_builds()
        return {k: after.get(k, 0) - before.get(k, 0)
                for k in set(after) | set(before)}

    delta1 = run_one()
    # the round builder keys on the (unique) policy -> guaranteed cold;
    # featurize/scatter/reset key only on shapes+cfg and may already be
    # cached by earlier tests in the same process — hence <= 1.
    assert delta1.get("sar_round", 0) == 1
    assert all(v <= 1 for v in delta1.values()), delta1

    delta2 = run_one()
    assert all(v == 0 for v in delta2.values()), \
        f"second identical engine recompiled builders: {delta2}"


# ----------------------------------------------------------------------
# bench history
# ----------------------------------------------------------------------
def test_history_record_roundtrip(tmp_path):
    p = tmp_path / "hist.jsonl"
    rec = history.record("unit_bench", {"m": 1.5}, path=p)
    rec2 = history.record_rows(
        "unit_bench", [("row_a", 12.0, "d=1")], path=p)
    assert rec["schema"] == history.SCHEMA_VERSION == 1
    fp = rec["fingerprint"]
    assert set(fp) >= {"backend", "device_kind", "jax", "python",
                       "interpret_mode"}
    assert isinstance(fp["interpret_mode"], bool)
    assert "ts" in rec and "git_sha" in rec
    assert rec2["metrics"]["row_a"]["us_per_call"] == 12.0

    loaded = history.load(p)
    assert len(loaded) == 2
    assert loaded[0]["metrics"] == {"m": 1.5}
    assert history.latest("unit_bench", p)["metrics"]["row_a"]
    assert history.latest("absent", p) is None
    assert history.load(tmp_path / "missing.jsonl") == []


def test_history_git_sha_present_in_repo():
    sha = history.git_sha()
    assert sha is None or (len(sha) == 40
                           and all(c in "0123456789abcdef" for c in sha))


# ----------------------------------------------------------------------
# regression gate
# ----------------------------------------------------------------------
BASE = {
    "serving.adaptive.decisions_per_s_warm": 100.0,
    "serving.adaptive.host_syncs_per_decision": 0.5,
    "serving.adaptive.flag_fraction": 0.25,
    "kernels.kernel_decision_fused.us_per_call_warm": 200.0,
    "kernels.fused.peak_vs_r_growth": 1.0,
}


def test_regress_identical_passes():
    assert regress.compare(dict(BASE), dict(BASE)) == []


def test_regress_catches_2x_wall_slowdown():
    cur = dict(BASE)
    cur["kernels.kernel_decision_fused.us_per_call_warm"] = 400.0
    fails = regress.compare(cur, BASE, wall_ratio=1.5)
    assert [f["metric"] for f in fails] == \
        ["kernels.kernel_decision_fused.us_per_call_warm"]
    # a generous CI ratio lets the same 2x through (honest wide band)
    assert regress.compare(cur, BASE, wall_ratio=5.0) == []


def test_regress_catches_throughput_drop_and_abs_band():
    cur = dict(BASE)
    cur["serving.adaptive.decisions_per_s_warm"] = 40.0    # < 100/1.5
    cur["serving.adaptive.flag_fraction"] = 0.45           # |d|>0.05
    fails = {f["metric"] for f in regress.compare(cur, BASE)}
    assert "serving.adaptive.decisions_per_s_warm" in fails
    assert "serving.adaptive.flag_fraction" in fails


def test_regress_missing_metric_is_failure():
    cur = dict(BASE)
    del cur["serving.adaptive.host_syncs_per_decision"]
    fails = regress.compare(cur, BASE)
    assert fails and fails[0]["kind"] == "missing"
    # extra current-only metrics are ignored until baseline refresh
    cur2 = dict(BASE, **{"serving.new.metric": 1.0})
    assert regress.compare(cur2, BASE) == []


def test_regress_deterministic_band_is_tight():
    cur = dict(BASE)
    cur["serving.adaptive.host_syncs_per_decision"] = 0.7  # > 0.5*1.25
    fails = regress.compare(cur, BASE, wall_ratio=100.0)
    assert [f["metric"] for f in fails] == \
        ["serving.adaptive.host_syncs_per_decision"]


def test_regress_floor_band_is_absolute():
    """FLOOR_BANDS gate on the committed constant, not the baseline
    value: a weak committed baseline must not weaken the gate, and a
    strong baseline must not tighten it into a wall-clock-style ratio."""
    base = {"fleet.scaling_efficiency_4pools": 2.0,
            "fleet.speedup_4pools": 8.0}
    # above the floors but far below baseline: still a PASS
    cur = {"fleet.scaling_efficiency_4pools": 0.75,
           "fleet.speedup_4pools": 3.5}
    assert regress.compare(cur, base, wall_ratio=1.0) == []
    # below a floor: FAIL even if the baseline were weaker than the floor
    cur["fleet.speedup_4pools"] = 2.9
    fails = regress.compare(cur, {**base, "fleet.speedup_4pools": 2.5},
                            wall_ratio=100.0)
    assert [f["metric"] for f in fails] == ["fleet.speedup_4pools"]
    assert fails[0]["kind"] == "floor" and fails[0]["limit"] == 3.0


def test_regress_current_metrics_extraction(tmp_path):
    serving = tmp_path / "s.json"
    kernels = tmp_path / "k.json"
    serving.write_text(json.dumps({"configs": {"adaptive": {
        "decisions_per_s_warm": 50.0, "flag_fraction": 0.2,
        "host_syncs_per_decision": 1.0, "model_decisions_per_s": 9.0,
        "mean_samples_per_decision": 6.0,
        "peak_live_bytes_per_decision": 4096.0,
        "energy_total_J": 1.0}}}))
    kernels.write_text(json.dumps({"rows": [
        {"name": "kernel_decision_fused", "us_per_call": 9.0,
         "us_per_call_warm": 8.0, "derived": ""},
        {"name": "kernel_decision_peak_vs_R_fused", "us_per_call": 0.0,
         "derived": "R8=1B;R64=1B;growth=1.00x"}]}))
    lifetime = tmp_path / "lt.json"
    lifetime.write_text(json.dumps({
        "serve": {"healed": {"lifetime": {"advisories": 1, "heals": 1}},
                  "fresh": {"lifetime": {"advisories": 0}}},
        "static": {"arms": {"healed": {"clean": {"acc_dev": 0.01}}}},
        "gates": {"healed_loop_closed": True, "stale_degraded": True}}))
    cur = regress.current_metrics(serving, kernels, lifetime)
    assert cur["serving.adaptive.decisions_per_s_warm"] == 50.0
    assert cur["kernels.kernel_decision_fused.us_per_call_warm"] == 8.0
    assert cur["kernels.fused.peak_vs_r_growth"] == 1.0
    assert cur["lifetime.serve_healed.heals"] == 1.0
    assert cur["lifetime.serve_fresh.false_advisories"] == 0.0
    assert cur["lifetime.static.healed_clean_acc_dev"] == 0.01
    assert cur["lifetime.gates_all_pass"] == 1.0
    assert "serving.adaptive.energy_total_J" not in cur   # not gated
    # fleet snapshot (BENCH_fleet.json) flattens per-pool structural
    # metrics plus the floor-gated scaling quantities
    fleet = tmp_path / "f.json"
    fleet.write_text(json.dumps({
        "pools": {"1": {"decisions_per_s_warm": 10.0,
                        "decisions_per_s_mesh": 11.0,
                        "host_syncs_per_decision": 0.03,
                        "per_pool_syncs_per_decision": 0.03},
                  "4": {"decisions_per_s_warm": 30.0,
                        "decisions_per_s_mesh": 44.0,
                        "host_syncs_per_decision": 0.01,
                        "per_pool_syncs_per_decision": 0.04}},
        "speedup_4pools": 4.0, "scaling_efficiency_4pools": 1.0}))
    cur = regress.current_metrics(serving, kernels, lifetime, fleet)
    assert cur["fleet.pools4.decisions_per_s_mesh"] == 44.0
    assert cur["fleet.pools1.per_pool_syncs_per_decision"] == 0.03
    assert cur["fleet.speedup_4pools"] == 4.0
    assert cur["fleet.scaling_efficiency_4pools"] == 1.0
    # SLO snapshot (BENCH_slo.json) contributes the gate boolean plus
    # the nominal-Poisson structural metrics
    slo = tmp_path / "s.json"
    slo.write_text(json.dumps({
        "gates": {"slo_report_well_formed": True,
                  "burn_alert_fires_under_spike": True,
                  "quiet_under_nominal": True, "gates_all_pass": True},
        "configs": {"poisson_engine": {
            "queue_wait_share": 0.3,
            "host_syncs_per_decision": 0.25}}}))
    cur = regress.current_metrics(serving, kernels, lifetime, fleet, slo)
    assert cur["slo.gates_all_pass"] == 1.0
    assert cur["slo.poisson_engine.queue_wait_share"] == 0.3
    assert cur["slo.poisson_engine.slo_syncs_per_decision"] == 0.25
    # no snapshots at all -> empty (regress exits 2 in main)
    assert regress.current_metrics(tmp_path / "a.json",
                                   tmp_path / "b.json",
                                   tmp_path / "c.json",
                                   tmp_path / "d.json",
                                   tmp_path / "e.json") == {}


def test_committed_baseline_gates_clean(tmp_path):
    """The committed baseline must pass against the committed BENCH
    snapshots — i.e. the repo ships in a green-gate state."""
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    serving, kernels = repo / "BENCH_serving.json", \
        repo / "BENCH_kernels.json"
    lifetime = repo / "BENCH_lifetime.json"
    if not (regress.BASELINE_PATH.exists() and serving.exists()
            and kernels.exists()):
        pytest.skip("no committed bench snapshots")
    cur = regress.current_metrics(serving, kernels, lifetime)
    fails = regress.compare(cur, regress.load_baseline(),
                            wall_ratio=1.0 + 1e-9)
    assert fails == [], fails


# ----------------------------------------------------------------------
# decision-path roofline
# ----------------------------------------------------------------------
def test_roofline_serving_cells():
    from benchmarks import roofline
    cells = roofline.serving_cells(
        points=((4, 8, 4),), measure_reps=2)
    names = [c["name"] for c in cells]
    assert any(n.startswith("decision_update_") for n in names)
    assert any(n.startswith("sar_round_") for n in names)
    for c in cells:
        assert c["bound"] in ("compute", "memory")
        assert c["bound_us"] > 0
        assert c["measured_us"] > 0
        assert c["flops"] > 0 and c["hbm_bytes"] > 0
        assert isinstance(c["interpret_mode"], bool)


# ----------------------------------------------------------------------
# Prometheus text-format edge cases (satellite: registry hardening)
# ----------------------------------------------------------------------
def _parse_prom(text):
    """Minimal exposition-format parser: {name{labels}: value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val)
    return out


def test_prometheus_label_escaping_roundtrip():
    reg = MetricsRegistry()
    nasty = 'a\\b"c\nd'
    reg.counter("decisions_total", 3, path=nasty)
    text = reg.to_prometheus()
    assert 'path="a\\\\b\\"c\\nd"' in text
    # a raw newline inside a label value would split the sample line
    body = [ln for ln in text.splitlines()
            if ln and not ln.startswith("#")]
    assert len(body) == 1
    assert _parse_prom(text)[
        'repro_decisions_total{path="a\\\\b\\"c\\nd"}'] == 3


def test_prometheus_nonfinite_histogram_counts_sanitized():
    reg = MetricsRegistry()
    reg.histogram("lat", [float("nan"), 2, float("inf")],
                  [0.0, 1.0, 2.0, 3.0])
    text = reg.to_prometheus()
    parsed = _parse_prom(text)
    assert parsed['repro_lat_bucket{le="1.0"}'] == 0     # nan -> 0
    assert parsed['repro_lat_bucket{le="2.0"}'] == 2
    assert parsed['repro_lat_bucket{le="3.0"}'] == 2     # inf -> 0
    assert parsed['repro_lat_bucket{le="+Inf"}'] == 2
    assert parsed["repro_lat_count"] == 2
    assert all(math.isfinite(v) for v in parsed.values())


def test_prometheus_empty_histogram_and_overflow():
    reg = MetricsRegistry()
    reg.histogram("empty", [], [0.0, 1.0])
    reg.histogram("over", [1, 1], [0.0, 0.5, 1.0], overflow=3,
                  sum=42.0)
    text = reg.to_prometheus()
    parsed = _parse_prom(text)
    assert parsed['repro_empty_bucket{le="+Inf"}'] == 0
    assert parsed["repro_empty_count"] == 0
    # overflow lands in +Inf (and only there) and counts in _count
    assert parsed['repro_over_bucket{le="1.0"}'] == 2
    assert parsed['repro_over_bucket{le="+Inf"}'] == 5
    assert parsed["repro_over_count"] == 5
    assert parsed["repro_over_sum"] == 42.0              # explicit sum


def test_prometheus_text_parse_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a_total", 7, job="x")
    reg.gauge("g", 0.5)
    reg.histogram("h", [1, 2], [0.0, 1.0, 2.0])
    prom, js = reg.write(str(tmp_path / "m"))
    parsed = _parse_prom(open(prom).read())
    assert parsed['repro_a_total{job="x"}'] == 7
    assert parsed["repro_g"] == 0.5
    assert parsed['repro_h_bucket{le="+Inf"}'] == 3
    assert json.loads(open(js).read())["metrics"]


def test_serving_registry_accepts_perf_sections():
    snap = {"admission": {"count": 2, "total_s": 1e-3, "mean_s": 5e-4,
                          "counts": [2] + [0] * 27, "overflow": 0,
                          "edges": list(np.logspace(-6, 1, 29))}}
    cc = {"builder_builds": {"sar_round": 1},
          "xla_compile_events": 10, "xla_compile_seconds": 0.5}
    costs = [{"name": "sar_round", "flops": 1e6, "hbm_bytes": 2e6,
              "peak_live_bytes": 65536, "compile_s": 0.1,
              "backend": "cpu"}]
    reg = serving_registry({"decisions": 0}, profile=snap,
                           compile_counters=cc, compiled_costs=costs)
    text = reg.to_prometheus()
    parsed = _parse_prom(text)
    assert 'repro_stage_latency_seconds_bucket' in text
    assert parsed['repro_engine_builder_builds_total'
                  '{builder="sar_round",job="serving"}'] == 1
    assert parsed['repro_xla_compile_events_total'
                  '{job="serving"}'] == 10
    assert parsed['repro_compiled_flops'
                  '{job="serving",fn="sar_round"}'] == 1e6
