"""The serving tick's stages in the ``jax.profiler`` trace.

``StageProfiler.span`` is the program's one span primitive: every stage
it times is also a trace annotation of the same name, so a profiler
capture shows the tick's stages on the device trace's clock.  These
tests serve a SAR engine inside a trace, read it back with
``ProfileData``, and hold the trace to the profiler:

  * every span of the tick tree lands on the driving thread, nested as
    drawn in ``SarServingEngine.step``;
  * each stage's span count equals the profiler's count, and the
    verdict pull is one span per array copied to the host;
  * a collection inside a tick is a ``gc`` span, and a full one a
    ``gc_full`` observation;
  * a disabled profiler emits nothing and serves the same verdicts.

Plus the profiler's own pieces: the collector hook's lifetime, the
histogram binning, and the pull helper the fleet shares.
"""

from __future__ import annotations

import bisect
import gc
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.launch.serve import make_sar_stream
from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
from repro.obs import prof
from repro.obs.prof import StageProfiler
from repro.serving import SarServingEngine, TriagePolicy
from repro.serving.fleet import SarServingFleet

CFG = SarCnnConfig()
POLICY = TriagePolicy(conf_threshold=0.7, mi_threshold=0.05,
                      r_min=4, r_max=20)
N_SLOTS = 32
N_REQUESTS = 80
# trace name -> the span it sits in directly, as drawn in step()
PARENT = {
    "admission": "sar_tick", "slot_mask": "sar_tick",
    "dispatch": "sar_tick", "triage_loop": "sar_tick",
    "retirement": "sar_tick",
    "admit_stack": "admission", "featurize": "admission",
    "admit_enqueue": "admission",
    "round_wait": "triage_loop", "verdict_pull": "triage_loop",
    "slo_fold": "retirement",
}
TREE = {"sar_tick", *PARENT}
STAGE = {"sar_tick": "tick"}          # trace name -> profiler stage
PULLS_PER_TICK = 11                   # verdict, 9 fin keys, rounds


@pytest.fixture(scope="module")
def params():
    return init_sar_cnn(jax.random.PRNGKey(3), CFG)


def _engine(params, profiler):
    eng = SarServingEngine(params, CFG, n_slots=N_SLOTS, policy=POLICY,
                           adaptive_mode=True, fused=True,
                           profiler=profiler)
    for r in make_sar_stream(N_REQUESTS, corrupt_frac=0.25):
        eng.submit(r)
    eng.start()
    return eng


def _serve(eng) -> int:
    """Step until the queue and the pool are empty; returns the ticks
    that dispatched a round."""
    served = 0
    while eng.step() or eng.queue:
        served += 1
    return served


def _read_trace(log_dir):
    """{window name: [(name, start_ns, end_ns)]} of the events on the
    thread that opened each ``*_window`` span, inside that span."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            for name, s, e in evs:
                if name.endswith("_window"):
                    out[name] = [ev for ev in evs if ev[0] != name
                                 and s <= ev[1] and ev[2] <= e]
    return out


def _innermost(ev, spans):
    """The latest-starting span of ``spans`` that contains ``ev``."""
    holders = [sp for sp in spans if sp is not ev
               and sp[1] <= ev[1] and ev[2] <= sp[2]]
    return max(holders, key=lambda sp: sp[1], default=None)


def _counts(after, before):
    return {k: v["count"] - before.get(k, {}).get("count", 0)
            for k, v in after.items()}


def test_tick_spans_in_trace_match_profiler(params, tmp_path):
    on = _engine(params, True)
    off = _engine(params, False)
    on.step()                      # compile outside the trace
    off.step()
    # one forced full collection inside a tick, in its slot_mask stage
    forced = []
    mask = on.active_mask

    def active_mask():
        if not forced:
            forced.append(gc.collect())
        return mask()

    was_enabled = gc.isenabled()
    gc.disable()                   # only the forced collection runs
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            before = on.profiler.snapshot()
            with TraceAnnotation("on_window"):
                on.step()
                on.active_mask = active_mask
                ticks = 1 + _serve(on)
            after = on.profiler.snapshot()
            with TraceAnnotation("off_window"):
                _serve(off)
        finally:
            jax.profiler.stop_trace()
    finally:
        if was_enabled:
            gc.enable()
    spans = _read_trace(str(tmp_path))
    ours = [ev for ev in spans["on_window"] if ev[0] in TREE]

    # nested as drawn: each span's innermost tree span is its parent
    names = {ev[0] for ev in ours}
    assert names == TREE, TREE - names
    for ev in ours:
        holder = _innermost(ev, ours)
        if ev[0] == "sar_tick":
            assert holder is None
        else:
            assert holder is not None and holder[0] == PARENT[ev[0]], \
                (ev, holder)

    # the trace's counts are the profiler's
    counts = _counts(after, before)
    for name in TREE:
        traced = sum(1 for ev in ours if ev[0] == name)
        assert traced == counts[STAGE.get(name, name)], name
    assert counts["triage_loop"] == ticks
    assert counts["verdict_pull"] == PULLS_PER_TICK * ticks
    assert counts["round_wait"] == ticks

    # the forced collection: a gc span in the tick's slot_mask stage,
    # observed as one full collection
    assert forced
    assert counts["gc"] == 1 and counts["gc_full"] == 1
    in_mask = [ev for ev in spans["on_window"] if ev[0] == "gc"
               and any(sp[0] == "slot_mask" and sp[1] <= ev[1]
                       and ev[2] <= sp[2] for sp in ours)]
    assert in_mask

    # the disabled profiler traces nothing and serves the same verdicts
    assert not [ev for ev in spans["off_window"] if ev[0] in TREE]
    assert off.profiler.snapshot() == {}
    assert on.host_syncs == off.host_syncs
    rec_on = {r.rid: r for r in on.metrics.records}
    rec_off = {r.rid: r for r in off.metrics.records}
    assert set(rec_on) == set(rec_off) == set(range(N_REQUESTS))
    for rid, a in rec_on.items():
        b = rec_off[rid]
        assert (a.verdict, a.prediction, a.n_samples, a.confidence,
                a.mutual_information) == \
            (b.verdict, b.prediction, b.n_samples, b.confidence,
             b.mutual_information), rid


def _hooks(profiler):
    return [cb for cb in gc.callbacks
            if getattr(cb, "profiler", None) is profiler]


def test_gc_hook_once_per_profiler_and_dropped_with_owners():
    class Owner:
        pass

    p = StageProfiler()
    a, b = Owner(), Owner()
    p.track_gc(a)
    p.track_gc(b)
    assert len(_hooks(p)) == 1
    gc.collect()
    snap = p.snapshot()
    assert snap["gc"]["count"] == 1
    assert snap["gc_full"]["count"] == 1
    del a
    assert len(_hooks(p)) == 1
    del b
    assert _hooks(p) == []
    gc.collect()
    assert p.snapshot()["gc"]["count"] == 1      # unhooked: no more


def test_engine_hook_leaves_with_the_engine(params):
    eng = SarServingEngine(params, CFG, n_slots=8, policy=POLICY)
    p = eng.profiler
    assert len(_hooks(p)) == 1
    del eng
    gc.collect()
    assert _hooks(p) == []
    off = SarServingEngine(params, CFG, n_slots=8, policy=POLICY,
                           profiler=False)
    assert _hooks(off.profiler) == []


def _binned_by_searchsorted(values):
    """The histogram as numpy's searchsorted bins it."""
    edges = prof._EDGES
    counts = np.zeros(len(edges) - 1, np.int64)
    over, total = 0, 0.0
    for v in values:
        v = max(float(v), 0.0)
        if np.isfinite(v):
            total += v
        if v >= edges[-1] or not np.isfinite(v):
            over += 1
            continue
        counts[np.searchsorted(edges, v, side="right") - 1
               if v >= edges[0] else 0] += 1
    return counts.tolist(), over, total


@pytest.mark.parametrize("kind", ["edges", "random", "extremes"])
def test_observe_bins_as_searchsorted(kind):
    edges = prof._EDGES
    if kind == "edges":
        values = list(edges) + [np.nextafter(e, 0) for e in edges] + \
            [np.nextafter(e, np.inf) for e in edges]
    elif kind == "random":
        values = 10 ** np.random.default_rng(0).uniform(-8, 2, 5000)
    else:
        values = [0.0, -1.0, 1e-12, 9.999999, 10.0, 1e9, float("inf")]
    p = StageProfiler()
    for v in values:
        p.observe("s", v)
    d = p.snapshot()["s"]
    counts, over, total = _binned_by_searchsorted(values)
    assert d["counts"] == counts
    assert d["overflow"] == over
    assert d["count"] == len(values)
    assert d["total_s"] == pytest.approx(total, rel=1e-12)
    assert d["edges"] == edges.tolist()
    assert bisect.bisect_right(prof._EDGE_LIST, edges[3]) == \
        np.searchsorted(edges, edges[3], side="right")


def test_fleet_shares_the_pull_split_and_one_gc_hook(params):
    fleet = SarServingFleet(params, CFG, n_pools=2, slots_per_pool=8,
                            policy=POLICY, gang=False)
    assert len(_hooks(fleet.profiler)) == 1
    for r in make_sar_stream(24, corrupt_frac=0.25, batch=8):
        fleet.submit(r)
    fleet.run()
    snap = fleet.profiler.snapshot()
    loops = snap["triage_loop"]["count"]
    assert loops == fleet.host_syncs
    assert snap["round_wait"]["count"] == loops
    assert snap["verdict_pull"]["count"] == PULLS_PER_TICK * loops
