"""SLO observability acceptance: the lifecycle tracker must be FREE at
the decision level, the load harness deterministic, and the alert bus
correct on both edges (fire under burn, quiet when nominal).

The load-bearing claims, mirroring tests/test_obs.py's telemetry
gates:

  1. zero overhead — SLO tracking on vs off: bit-identical verdicts,
     the SAME host-sync count, and the SAME compiled round executable
     (``lru_cache`` identity — the builders never see the tracker), on
     the engine AND the fleet path;
  2. the numbers are CORRECT — per-request queue-wait + service
     decomposition reconciles against total latency and the wall span;
     histogram quantiles agree with numpy on the raw samples to within
     a bucket;
  3. the seeded arrival generators are deterministic and hit their
     mean rates;
  4. one fleet trace is a SINGLE stitched timeline: per-pool process
     tracks, router tick spans, and matched flow start/end pairs per
     request;
  5. the alert bus pages on SLO burn and backpressure and stays quiet
     otherwise, and its advisories export through the Prometheus
     registry.
"""

import json
import math

import jax
import numpy as np
import pytest

from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
from repro.obs.alerts import AlertBus
from repro.obs.registry import MetricsRegistry, add_alerts, add_slo, \
    quantile
from repro.obs.slo import NULL_SLO, SLO, SloTracker, _EDGES, _Hist, \
    _VERDICTS
from repro.obs.trace import Tracer
from repro.serving import TriagePolicy
from repro.serving.load import ArrivalSpec, run_open_loop
from repro.serving.metrics import RequestRecord

POLICY = TriagePolicy(conf_threshold=0.7, mi_threshold=0.05,
                      r_min=4, r_max=20)


@pytest.fixture(scope="module")
def sar():
    cfg = SarCnnConfig()
    return init_sar_cnn(jax.random.PRNGKey(3), cfg), cfg


def _stream(n):
    from repro.launch.serve import make_sar_stream
    return make_sar_stream(n, corrupt_frac=0.25, corruption="fog")


def _engine(sar, *, slo=True, n_slots=8, tracer=None):
    from repro.serving import SarServingEngine
    params, cfg = sar
    return SarServingEngine(params, cfg, n_slots=n_slots, policy=POLICY,
                            adaptive_mode=True, fused=True,
                            telemetry=False, slo=slo, tracer=tracer)


def _fleet(sar, *, slo=True, tracer=None, n_pools=2, slots=4):
    from repro.serving import SarServingFleet
    params, cfg = sar
    return SarServingFleet(params, cfg, n_pools=n_pools,
                           slots_per_pool=slots, policy=POLICY,
                           adaptive_mode=True, fused=True,
                           telemetry=False, gang=False, slo=slo,
                           tracer=tracer)


def _records_match(eng_a, eng_b, n_requests):
    recs_a = {r.rid: r for r in eng_a.metrics.records}
    recs_b = {r.rid: r for r in eng_b.metrics.records}
    assert set(recs_a) == set(recs_b) == set(range(n_requests))
    for rid in recs_a:
        a, b = recs_a[rid], recs_b[rid]
        assert a.verdict == b.verdict, rid
        assert a.prediction == b.prediction, rid
        assert a.n_samples == b.n_samples, rid


# ----------------------------------------------------------------------
# registry.quantile: log-bucket interpolation vs numpy
# ----------------------------------------------------------------------
def test_quantile_matches_numpy_within_a_bucket():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-5.0, sigma=1.5, size=4000)
    h = SloTracker()
    for s in samples:
        h._ttv.observe(float(s))
    hist = h._ttv.to_dict()
    edges = np.asarray(hist["edges"])
    for q in (0.5, 0.9, 0.95, 0.99):
        est = quantile(hist, q)
        exact = float(np.quantile(samples, q))
        # the estimate must land within one log bucket of the truth
        ratio = edges[1] / edges[0]
        assert exact / ratio <= est <= exact * ratio, (q, est, exact)


def test_quantile_edge_cases():
    empty = {"counts": [0, 0], "edges": [0.1, 1.0, 10.0], "overflow": 0}
    assert math.isnan(quantile(empty, 0.5))
    over = {"counts": [0, 0], "edges": [0.1, 1.0, 10.0], "overflow": 5}
    assert quantile(over, 0.5) == 10.0          # overflow -> last edge
    one = {"counts": [4, 0], "edges": [0.1, 1.0, 10.0], "overflow": 0}
    v = quantile(one, 0.5)
    assert 0.1 <= v <= 1.0


# ----------------------------------------------------------------------
# SLO spec parsing + burn-rate math
# ----------------------------------------------------------------------
def test_slo_parse_and_burn_math():
    s = SLO.parse("0.25:p99")
    assert s.target_s == 0.25 and s.percentile == 0.99
    assert s.name == "p99<=0.25s"
    assert abs(s.error_budget - 0.01) < 1e-9
    # 5 violations in 100 at a 1% budget -> burn 5x -> breach at 2x
    ev = s.evaluate(5, 100)
    assert abs(ev["burn_rate"] - 5.0) < 1e-6
    assert ev["breach"] is True
    # exactly on budget: burn 1x, no breach
    ev = s.evaluate(1, 100)
    assert abs(ev["burn_rate"] - 1.0) < 1e-6
    assert ev["breach"] is False
    # custom burn threshold rides the spec string
    s = SLO.parse("1.5:p95:4")
    assert s.burn_alert == 4.0
    assert s.evaluate(10, 100)["breach"] is False      # burn 2x < 4x
    # no requests -> no breach
    assert SLO.parse("0.1:p99").evaluate(0, 0)["breach"] is False


def test_slo_bad_specs_raise():
    with pytest.raises(ValueError):
        SLO.parse("0.25:q99")
    with pytest.raises(ValueError):
        SLO.parse("fast:p99")
    # bare target defaults to p99
    assert SLO.parse("0.25").percentile == 0.99


# ----------------------------------------------------------------------
# arrival generators: determinism + mean rates
# ----------------------------------------------------------------------
def test_arrival_specs_deterministic_and_rated():
    # ramp: time per request is 1/rate_i, so the realized overall rate
    # is the log-mean (80-20)/ln(80/20) = 43.28 req/s
    for spec_str, mean in (("poisson:50", 50.0), ("burst:50", 50.0),
                           ("burst:50:4", 50.0),
                           ("ramp:20:80", 60.0 / math.log(4.0))):
        spec = ArrivalSpec.parse(spec_str)
        assert spec.mean_rate == pytest.approx(mean)
        a = spec.offsets(4000, seed=3)
        b = spec.offsets(4000, seed=3)
        np.testing.assert_array_equal(a, b)          # same seed, same
        c = spec.offsets(4000, seed=4)
        assert not np.array_equal(a, c)              # new seed, new
        assert np.all(np.diff(a) >= 0)               # ascending
        measured = len(a) / a[-1]
        assert measured == pytest.approx(mean, rel=0.1), spec_str


def test_burst_spec_actually_bursts():
    spec = ArrivalSpec.parse("burst:100:10")
    gaps = np.diff(np.concatenate([[0.0], spec.offsets(640, seed=0)]))
    group = (np.arange(640) // 16) % 2
    burst_mean = gaps[group == 0].mean()
    lull_mean = gaps[group == 1].mean()
    assert lull_mean > 5 * burst_mean


def test_arrival_parse_rejects_unknown():
    with pytest.raises(ValueError):
        ArrivalSpec.parse("uniform:5")


# ----------------------------------------------------------------------
# 1. zero-overhead gates: engine, fleet
# ----------------------------------------------------------------------
def test_engine_slo_zero_overhead(sar):
    n = 24
    eng_on = _engine(sar, slo=True)
    eng_off = _engine(sar, slo=False)
    for e in (eng_on, eng_off):
        for r in _stream(n):
            e.submit(r)
        e.run()
    _records_match(eng_on, eng_off, n)
    assert eng_on.host_syncs == eng_off.host_syncs
    # the compiled round executable is the SAME cached object — the
    # builders never see the tracker, so the graph cannot differ
    assert eng_on._round is eng_off._round
    assert eng_off.slo is NULL_SLO
    assert eng_off.slo.snapshot() == {}
    snap = eng_on.slo.snapshot()
    assert snap["requests"] == n
    assert snap["time_to_verdict"]["count"] == n
    by_verdict_n = sum(v["count"] for v in snap["by_verdict"].values())
    assert by_verdict_n == n


def test_fleet_slo_zero_overhead(sar):
    n = 24
    fl_on = _fleet(sar, slo=True)
    fl_off = _fleet(sar, slo=False)
    outs = []
    for fl in (fl_on, fl_off):
        for r in _stream(n):
            fl.submit(r)
        outs.append(fl.run())
    recs_on = {r.rid: r for e in fl_on.engines
               for r in e.metrics.records}
    recs_off = {r.rid: r for e in fl_off.engines
                for r in e.metrics.records}
    assert set(recs_on) == set(recs_off) == set(range(n))
    for rid in recs_on:
        assert recs_on[rid].verdict == recs_off[rid].verdict
        assert recs_on[rid].n_samples == recs_off[rid].n_samples
    assert fl_on.host_syncs == fl_off.host_syncs
    snap = outs[0]["slo"]
    assert snap["requests"] == n
    assert snap["fleet"]["ticks"] >= 1
    assert len(snap["fleet"]["queue_depth_peak"]) == fl_on.n_pools
    assert "slo" not in outs[1]


def test_mission_summary_unchanged_by_alert_bus():
    """The mission bus is post-hoc: feeding it must not mutate the
    summary it reads."""
    summary = {"decisions": 10, "rescued": 1}
    telem = {"g0": {"drift": {"drifted": True, "advisory": "drift!",
                              "z_mean": 9.0, "z_std": 1.0, "n": 64}}}
    before = json.dumps(telem, sort_keys=True) + json.dumps(summary,
                                                            sort_keys=True)
    bus = AlertBus()
    for g, t in telem.items():
        bus.observe_drift(t["drift"], source=f"mission/{g}")
    assert len(bus) == 1 and bus.advisories[0].kind == "drift"
    after = json.dumps(telem, sort_keys=True) + json.dumps(summary,
                                                           sort_keys=True)
    assert before == after


# ----------------------------------------------------------------------
# 2. queue/service decomposition reconciles
# ----------------------------------------------------------------------
def test_queue_plus_service_reconciles_with_latency(sar):
    n = 16
    eng = _engine(sar, slo=True)
    for r in _stream(n):
        eng.submit(r)
    out = eng.run()
    span = out["slo"]["span_s"]
    for rec in eng.metrics.records:
        q, s, tot = rec.queue_latency_s, rec.service_latency_s, \
            rec.latency_s
        assert q >= 0 and s >= 0
        assert q + s == pytest.approx(tot, rel=1e-6, abs=1e-9)
        assert tot <= span + 1e-3
        # verdict stamp: taken at the sync INSIDE the last dispatch, so
        # it can only precede the retire-side stamp
        assert rec.verdict_latency_s <= tot + 1e-9
    summ = eng.metrics.summary()
    assert summ["queue_wait_total_s"] + summ["service_total_s"] == \
        pytest.approx(sum(r.latency_s for r in eng.metrics.records),
                      rel=1e-6)
    assert 0.0 <= summ["queue_wait_share"] <= 1.0


# ----------------------------------------------------------------------
# 3. open-loop harness
# ----------------------------------------------------------------------
def test_open_loop_engine_and_snapshot(sar):
    n = 16
    eng = _engine(sar, slo=True, n_slots=4)
    reqs = _stream(n)
    spec = ArrivalSpec.parse("poisson:400")
    out = run_open_loop(eng, reqs, spec.offsets(n, seed=0))
    assert out["requests"] == n
    assert out["offered"]["submitted"] == n
    snap = out["slo"]
    assert snap["requests"] == n
    assert snap["p50_s"] <= snap["p95_s"] <= snap["p99_s"]
    assert math.isfinite(snap["mean_s"])


def test_slo_tracker_targets_and_breach(sar):
    n = 12
    tracker = SloTracker(slos=("1e9:p50", "1e-9:p99"))
    eng = _engine(sar, slo=tracker, n_slots=4)
    for r in _stream(n):
        eng.submit(r)
    eng.run()
    snap = tracker.snapshot()
    results = {s["name"]: s for s in snap["slos"]}
    huge = results["p50<=1e+09s"]
    tiny = results["p99<=1e-09s"]
    assert huge["violations"] == 0 and huge["breach"] is False
    assert tiny["violations"] == n and tiny["breach"] is True
    assert tiny["attainment"] == 0.0


# ----------------------------------------------------------------------
# 4. fleet trace stitching
# ----------------------------------------------------------------------
def test_fleet_trace_single_stitched_timeline(sar):
    n = 16
    tr = Tracer("fleet-test")
    fl = _fleet(sar, tracer=tr)
    for r in _stream(n):
        fl.submit(r)
    fl.run()
    doc = tr.to_chrome()
    ev = doc["traceEvents"]
    # per-pool process tracks, named
    pnames = {e["pid"]: e["args"]["name"] for e in ev
              if e["ph"] == "M" and e["name"] == "process_name"}
    assert pnames[0] == "router"
    for p in range(fl.n_pools):
        assert pnames[p + 1] == f"pool {p}"
    # router tick spans + per-pool gang-dispatch spans
    assert any(e["ph"] == "X" and e["name"] == "fleet_tick"
               and e["pid"] == 0 for e in ev)
    disp_pids = {e["pid"] for e in ev
                 if e["ph"] == "X" and e["name"] == "gang_dispatch"}
    assert disp_pids and disp_pids <= {p + 1
                                       for p in range(fl.n_pools)}
    # request flows: every rid has a start on the router track and an
    # end on some pool's slot track, with matching flow ids
    starts = {e["id"]: e for e in ev if e["ph"] == "s"}
    ends = {e["id"]: e for e in ev if e["ph"] == "f"}
    assert set(starts) == set(ends) == set(range(n))
    for rid in range(n):
        assert starts[rid]["pid"] == 0
        assert ends[rid]["pid"] in range(1, fl.n_pools + 1)
        assert ends[rid]["bp"] == "e"
        assert starts[rid]["ts"] <= ends[rid]["ts"]
    # request spans live on the pool that the router recorded
    req_spans = {e["name"]: e for e in ev
                 if e["ph"] == "X" and e["name"].startswith("req ")}
    for rid, pool in fl.routes.items():
        assert req_spans[f"req {rid}"]["pid"] == pool + 1
    json.dumps(doc)                                   # valid JSON


# ----------------------------------------------------------------------
# 5. alert bus
# ----------------------------------------------------------------------
def test_alert_bus_slo_burn_fires_and_quiet():
    bus = AlertBus()
    breached = {"slos": [
        {"name": "p99<=0.25s", "breach": True, "burn_rate": 8.0,
         "burn_alert": 2.0, "violations": 9, "requests": 100},
        {"name": "p50<=1s", "breach": False, "burn_rate": 0.1,
         "burn_alert": 2.0, "violations": 0, "requests": 100}]}
    bus.observe_slo(breached, source="test")
    assert bus.counts() == {"slo_burn": 1}
    assert bus.worst_severity() == "critical"
    quiet = AlertBus()
    quiet.observe_slo({"slos": [breached["slos"][1]]}, source="test")
    quiet.observe_drift({"drifted": False}, source="test")
    quiet.observe_backpressure({"fleet": {"backpressure_ticks": 0,
                                          "ticks": 9}})
    assert len(quiet) == 0


def test_alert_bus_backpressure_severity_scales():
    bus = AlertBus()
    bus.observe_backpressure({"fleet": {"backpressure_ticks": 1,
                                        "ticks": 10,
                                        "backlog_peak": 3}})
    bus.observe_backpressure({"fleet": {"backpressure_ticks": 9,
                                        "ticks": 10,
                                        "backlog_peak": 40}})
    sev = [a.severity for a in bus.advisories]
    assert sev == ["warning", "critical"]


def test_alert_bus_heal_and_drift_dialects():
    bus = AlertBus()
    bus.observe_drift({"drifted": True, "advisory": "recalibrate",
                       "z_mean": 7.5, "z_std": 2.0, "n": 128},
                      source="serve_sar")
    bus.observe_heal({"age_s": 3.0e7, "calib_epoch": 2, "z_mean": 7.5,
                      "z_std": 2.0, "advisory": ""}, source="lifetime")
    assert bus.counts() == {"drift": 1, "heal": 1}
    js = bus.to_json()
    assert js[0]["message"] == "recalibrate"
    assert js[1]["fields"]["calib_epoch"] == 2
    json.dumps(js)


def test_registry_exports_slo_and_alerts(sar, tmp_path):
    n = 12
    eng = _engine(sar, slo=True, n_slots=4)
    for r in _stream(n):
        eng.submit(r)
    out = eng.run()
    reg = MetricsRegistry()
    add_slo(reg, out["slo"], job="test")
    bus = AlertBus()
    bus.emit("slo_burn", "critical", "test", "burning")
    add_alerts(reg, bus.to_json(), job="test")
    text = reg.to_prometheus()
    assert "slo_requests_total" in text
    assert "slo_time_to_verdict_seconds_bucket" in text
    assert 'le="+Inf"' in text
    assert "alerts_total" in text
    assert 'kind="slo_burn"' in text
    prom, js = reg.write(str(tmp_path / "m"))
    doc = json.loads((tmp_path / "m.json").read_text())
    assert any(m["name"].endswith("slo_requests_total")
               for m in doc["metrics"])
    assert any(m["name"].endswith("alerts_total")
               for m in doc["metrics"])


def test_null_slo_is_inert():
    NULL_SLO.observe(object())
    NULL_SLO.observe_router(0.1)
    NULL_SLO.sample_queues([1], [1], 2)
    NULL_SLO.backpressure(5)
    assert NULL_SLO.snapshot() == {}
    assert not NULL_SLO.enabled


def test_slo_hist_edges_cover_wide_range():
    t = SloTracker()
    t._ttv.observe(1e-7)      # below first edge
    t._ttv.observe(float("nan"))
    t._ttv.observe(-1.0)
    t._ttv.observe(1e3)       # overflow
    d = t._ttv.to_dict()
    assert d["count"] == 3    # NaN dropped
    assert d["overflow"] == 1
    assert sum(d["counts"]) + d["overflow"] == 3
    assert len(d["edges"]) == len(_EDGES)


# ----------------------------------------------------------------------
# 6. batch fold: observe_many equals the per-record fold
# ----------------------------------------------------------------------
def _fold_one_by_one(tracker, recs):
    """The per-record fold as ``SloTracker.observe`` did it before the
    batch fold: five scalar ``_Hist.observe`` calls per record."""
    for rec in recs:
        t = rec.verdict_latency_s
        if math.isnan(t):
            t = rec.latency_s
        tracker._n += 1
        tracker._ttv.observe(t)
        tracker._queue.observe(rec.queue_latency_s)
        tracker._service.observe(rec.service_latency_s)
        name = _VERDICTS.get(int(rec.verdict), str(int(rec.verdict)))
        tracker._by_verdict.setdefault(name, _Hist()).observe(t)
        r = int(round(rec.n_samples / max(rec.n_decisions, 1)))
        tracker._by_r.setdefault(r, _Hist()).observe(t)
        for k, slo in enumerate(tracker.slos):
            if t > slo.target_s:
                tracker._violations[k] += 1
        arr = rec.arrival_pc
        if math.isnan(arr):
            arr = rec.arrival_s
        tracker._first_arrival = min(tracker._first_arrival, arr)
        tracker._last_done = max(tracker._last_done, rec.done_s)


def _tick_records(n, *, seed=0, verdicts=(0, 2), r_values=(4,),
                  verdict_nan=0.0, scale=1e-3, sign=1.0):
    """``n`` records shaped like one tick's retirements: arrival, then
    admission, verdict and done each about ``scale`` seconds later
    (earlier, with ``sign`` -1)."""
    rng = np.random.default_rng(seed)
    arrival = 100.0 - rng.uniform(0.0, 50 * scale, n)
    admit = arrival + sign * rng.exponential(scale, n)
    v_s = admit + sign * rng.exponential(scale, n)
    done = v_s + sign * rng.exponential(scale / 10, n)
    recs = []
    for i in range(n):
        r = int(rng.choice(r_values))
        dec = int(rng.integers(1, 4))
        recs.append(RequestRecord(
            rid=i, verdict=int(rng.choice(verdicts)),
            n_samples=r * dec, n_decisions=dec,
            arrival_s=1.7e9 + arrival[i], admit_s=float(admit[i]),
            done_s=float(done[i]), arrival_pc=float(arrival[i]),
            verdict_s=(float("nan") if rng.random() < verdict_nan
                       else float(v_s[i]))))
    return recs


def _hand_records():
    """Negative intervals, past-the-last-edge intervals, NaN stamps and
    records with only the wall-clock trio."""
    nan = float("nan")
    return [
        # admitted "before" arrival and a verdict before admission
        RequestRecord(rid=0, verdict=0, n_samples=4, n_decisions=1,
                      arrival_s=0.0, admit_s=9.0, done_s=10.0,
                      arrival_pc=10.0, verdict_s=8.0),
        RequestRecord(rid=1, verdict=2, n_samples=8, n_decisions=1,
                      arrival_s=5.0, admit_s=4.0, done_s=3.0),
        # 100 s is the last edge: these go to overflow
        RequestRecord(rid=2, verdict=1, n_samples=20, n_decisions=1,
                      arrival_s=0.0, admit_s=150.0, done_s=400.0,
                      arrival_pc=0.0, verdict_s=399.0),
        RequestRecord(rid=3, verdict=0, n_samples=6, n_decisions=4,
                      arrival_s=0.0, admit_s=100.0, done_s=250.0,
                      verdict_s=nan),
        # an infinite monotonic stamp falls back to the wall clock
        RequestRecord(rid=4, verdict=7, n_samples=5, n_decisions=0,
                      arrival_s=1.0, admit_s=1.5, done_s=2.0,
                      arrival_pc=float("inf"), verdict_s=1.75),
        RequestRecord(rid=5, verdict=2, n_samples=10, n_decisions=4,
                      arrival_s=nan, admit_s=1.0, done_s=nan,
                      verdict_s=nan),
    ]


FOLD_CASES = {
    "tick": lambda: _tick_records(954),
    "nan_verdict_stamp": lambda: _tick_records(64, seed=1,
                                               verdict_nan=0.5),
    "negative_intervals": lambda: _hand_records()[:2]
    + _tick_records(16, seed=2, sign=-1.0),
    "past_last_edge": lambda: _hand_records()[2:4]
    + _tick_records(16, seed=3, scale=30.0),
    "mixed_verdicts_and_r": lambda: _tick_records(
        256, seed=4, verdicts=(0, 1, 2), r_values=(4, 8, 12, 20))
    + _hand_records(),
    "one": lambda: _tick_records(1, seed=5),
    "empty": lambda: [],
}


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_observe_many_equals_per_record_fold(case):
    recs = FOLD_CASES[case]()
    # 0.75 s is the hand-made record 4's time to verdict exactly
    slos = ("1e-3:p99", "0.5:p50", "0.75:p99", "1e9:p99")
    batch, one = SloTracker(slos), SloTracker(slos)
    batch.observe_many(recs)
    batch.observe_many([])
    _fold_one_by_one(one, recs)
    a, b = batch.snapshot(), one.snapshot()
    assert a.keys() == b.keys()
    if not recs:
        assert a == {}
        return

    def same_hist(x, y):
        assert x["counts"] == y["counts"]
        assert x["overflow"] == y["overflow"]
        assert x["count"] == y["count"]
        assert x["total_s"] == pytest.approx(y["total_s"], rel=1e-12,
                                             abs=0.0)

    assert a["requests"] == b["requests"] == len(recs)
    for key in ("time_to_verdict", "queue_wait", "service"):
        same_hist(a[key], b[key])
    for key in ("by_verdict", "by_r"):
        assert list(a[key]) == list(b[key])
        for k in a[key]:
            same_hist(a[key][k], b[key][k])
    assert [s["violations"] for s in a["slos"]] == \
        [s["violations"] for s in b["slos"]]
    assert a["span_s"] == b["span_s"] or \
        (math.isnan(a["span_s"]) and math.isnan(b["span_s"]))


def test_observe_is_a_batch_of_one():
    recs = _tick_records(32, seed=6, verdicts=(0, 1, 2),
                         r_values=(4, 20))
    each, batch = SloTracker(("1e-3:p99",)), SloTracker(("1e-3:p99",))
    for rec in recs:
        each.observe(rec)
    batch.observe_many(recs)
    a, b = each.snapshot(), batch.snapshot()
    assert a["time_to_verdict"]["counts"] == \
        b["time_to_verdict"]["counts"]
    assert a["slos"] == b["slos"]
    assert a["by_r"].keys() == b["by_r"].keys()


def test_engine_folds_each_retiring_tick_once(sar):
    n = 24
    eng = _engine(sar, slo=True)
    for r in _stream(n):
        eng.submit(r)
    eng.run()
    recs = eng.metrics.records
    # every record of a tick carries that tick's verdict stamp
    retiring_ticks = len({r.verdict_s for r in recs})
    assert 1 < retiring_ticks < len(recs)
    prof_snap = eng.profiler.snapshot()
    assert prof_snap["slo_fold"]["count"] == retiring_ticks
    assert prof_snap["retirement"]["count"] >= retiring_ticks
    assert eng.slo.snapshot()["requests"] == len(recs) == n
