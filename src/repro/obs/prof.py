"""Performance observability: stage profiler, compile-event counters,
and a compiled-cost registry for the serving/mission hot path.

The repo's headline numbers are performance claims (185 TOPS/W/mm²,
640 aJ/sample, ~0.05 host syncs/decision), so performance itself needs
the same treatment obs/telemetry gave correctness: measured, exported,
and diffable run over run.  Three instruments, all host-side — none of
them touches the device-resident fast path, adds a host sync, or
changes a compiled graph (asserted in tests/test_obs.py):

``StageProfiler``
    Per-stage latency histograms over the serving loop's phases —
    admission, featurize, dispatch, triage_loop (the blocking
    device→host verdict pull, i.e. where the device-resident escalation
    time actually shows up on the host), retirement — on log-spaced
    buckets.  Stages are open-ended strings so the mission driver can
    profile its own phases (detector / rollout / drain) through the
    same exporter.  Exported as Prometheus histograms via
    ``obs.registry.add_stage_profile``.  ``span`` is the program's one
    span primitive: each span is also a ``jax.profiler`` trace
    annotation of the same name, so a ``--profile`` capture shows the
    stages on the device trace's clock.  ``track_gc`` adds every
    garbage collection as a ``gc`` span.

Compile-event counters
    ``count_build(name)`` ticks once per *executable construction* in
    ``serving/engine.py``'s ``lru_cache`` builders — two engines with
    identical frozen configs must tick each builder exactly once
    (tests/test_perf_obs.py).  A ``jax.monitoring`` listener
    additionally counts every XLA backend compile in the process
    (``xla_compile_events()`` / ``xla_compile_seconds()``), so a
    recompilation storm — shape drift re-jitting the pool functions
    80× — is a visible counter, not a silent slowdown.

``CostRegistry`` / ``compiled_cost``
    AOT-lowers a jitted function at given arg shapes and records XLA's
    own ``cost_analysis()`` (flops / bytes accessed) next to the
    loop-aware ``launch/hlo_analysis`` walk (flops, HBM bytes, largest
    live intermediate) and the compile wall time.  benchmarks/roofline
    charts these against peak; engines expose ``compiled_cost_records``
    so ``--profile`` runs capture the real deployed shapes.

``trace_capture``
    A context manager around ``jax.profiler.start_trace/stop_trace``
    (the programmatic XLA profiler): ``--profile DIR`` on
    ``launch/serve.py`` / ``launch/mission.py`` wraps the whole run and
    writes a TensorBoard-loadable trace directory.
"""

from __future__ import annotations

import bisect
import contextlib
import gc
import math
import time
import weakref
from collections import Counter
from typing import Any, Callable

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

# ----------------------------------------------------------------------
# stage profiler
# ----------------------------------------------------------------------
# The serving engines' hot-loop phases, in loop order.  StageProfiler
# accepts any stage string; this tuple just fixes the export order for
# the stages both engines share.
SERVING_STAGES = ("admission", "featurize", "dispatch", "triage_loop",
                  "retirement")

# Log-spaced latency edges: 1 µs .. 10 s, 4 buckets per decade.  Wide
# enough for interpret-mode CPU dispatches and tight enough that a TPU
# round's sub-ms latencies don't all land in one bin.
_EDGES = np.logspace(-6, 1, 29)
# The same edges as Python floats: ``observe`` bins one float at a time,
# where ``bisect`` costs a fraction of a numpy call.
_EDGE_LIST = _EDGES.tolist()


class _Span:
    """One timed stage: a trace annotation around a perf_counter pair."""

    __slots__ = ("_observe", "_stage", "_trace", "_t0")

    def __init__(self, observe, stage: str, trace):
        self._observe = observe
        self._stage = stage
        self._trace = trace

    def __enter__(self):
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        self._observe(self._stage, dt)
        return False


class _GcSpans:
    """``gc.callbacks`` hook: every collection is a ``gc`` trace span and a
    ``gc`` observation in ``profiler`` (a full one, generation 2, also a
    ``gc_full`` one).  ``holders`` counts the owners keeping it hooked."""

    def __init__(self, profiler: "StageProfiler"):
        self.profiler = profiler
        self.holders = 0
        self._trace = None
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._trace = TraceAnnotation("gc")
            self._trace.__enter__()
            self._t0 = time.perf_counter()
            return
        if self._trace is None:          # hooked in mid-collection
            return
        dt = time.perf_counter() - self._t0
        self.close()
        self.profiler.observe("gc", dt)
        if info.get("generation") == 2:
            self.profiler.observe("gc_full", dt)

    def close(self) -> None:
        """End the open trace span, if a collection is under way."""
        if self._trace is not None:
            self._trace.__exit__(None, None, None)
            self._trace = None


class StageProfiler:
    """Host-side per-stage latency histograms (perf_counter clocks).

    Purely host arithmetic on scalars already measured by the engine
    loop — no device interaction, so it cannot add host syncs or
    perturb compiled graphs.  ``snapshot()`` is JSON-ready and feeds
    ``obs.registry.add_stage_profile``.
    """

    edges = _EDGES

    def __init__(self):
        self._counts: dict[str, list[int]] = {}
        self._over: dict[str, int] = {}
        self._total_s: dict[str, float] = {}
        self._n: dict[str, int] = {}
        self._gc_hook: _GcSpans | None = None

    @property
    def enabled(self) -> bool:
        return True

    def observe(self, stage: str, dt_s: float) -> None:
        """Fold one latency observation into ``stage``'s histogram.

        NaN observations are dropped; negative ones clamp to 0; +inf
        lands in the overflow (+Inf) bucket — the registry exporter
        keeps ``_count`` exact either way."""
        if dt_s != dt_s:                               # NaN
            return
        dt_s = max(float(dt_s), 0.0)
        if stage not in self._counts:
            self._counts[stage] = [0] * (len(_EDGE_LIST) - 1)
            self._over[stage] = self._n[stage] = 0
            self._total_s[stage] = 0.0
        self._n[stage] += 1
        if dt_s != math.inf:
            self._total_s[stage] += dt_s
        if dt_s >= _EDGE_LIST[-1]:                     # +inf included
            self._over[stage] += 1
            return
        self._counts[stage][
            bisect.bisect_right(_EDGE_LIST, dt_s) - 1 if
            dt_s >= _EDGE_LIST[0] else 0] += 1

    def span(self, stage: str, *, name: str | None = None,
             step: int | None = None) -> _Span:
        """Time the enclosed work as one ``stage`` observation, inside a
        ``jax.profiler`` trace annotation named ``name`` (default: the
        stage).  With ``step`` the annotation is a
        ``StepTraceAnnotation`` carrying that step number."""
        label = name or stage
        trace = (TraceAnnotation(label) if step is None
                 else StepTraceAnnotation(label, step_num=step))
        return _Span(self.observe, stage, trace)

    def track_gc(self, owner) -> None:
        """Observe every garbage collection of the process (stage
        ``gc``; full ones also ``gc_full``) as a ``gc`` span, for as long
        as ``owner`` lives.  One hook per profiler, however many owners
        share it, so a shared profiler counts each pause once; the hook
        leaves ``gc.callbacks`` when its last owner is dropped."""
        if self._gc_hook is None:
            self._gc_hook = _GcSpans(self)
            gc.callbacks.append(self._gc_hook)
        self._gc_hook.holders += 1
        weakref.finalize(owner, self._release_gc)

    def _release_gc(self) -> None:
        hook = self._gc_hook
        hook.holders -= 1
        if hook.holders == 0:
            # a finalizer may run inside a collection: close its span
            hook.close()
            gc.callbacks.remove(hook)
            self._gc_hook = None

    def snapshot(self) -> dict[str, Any]:
        """{stage: {count, total_s, mean_s, p50/p95/p99_s, counts,
        overflow, edges}} — percentiles read straight from the binned
        histogram via the shared registry.quantile interpolator."""
        from repro.obs.registry import quantile
        out: dict[str, Any] = {}
        order = [s for s in SERVING_STAGES if s in self._counts]
        order += [s for s in self._counts if s not in SERVING_STAGES]
        for stage in order:
            n = self._n[stage]
            rec = {
                "count": n,
                "total_s": float(self._total_s[stage]),
                "mean_s": float(self._total_s[stage]) / n if n else
                float("nan"),
                "counts": list(self._counts[stage]),
                "overflow": self._over[stage],
                "edges": list(_EDGE_LIST),
            }
            rec["p50_s"] = quantile(rec, 0.50)
            rec["p95_s"] = quantile(rec, 0.95)
            rec["p99_s"] = quantile(rec, 0.99)
            out[stage] = rec
        return out


class _NullStageProfiler(StageProfiler):
    """No-op profiler so engine call sites never branch: it times
    nothing, traces nothing and hooks no collector."""

    @property
    def enabled(self) -> bool:
        return False

    def observe(self, stage, dt_s):
        pass

    def span(self, stage, *, name=None, step=None):
        return _NULL_SPAN

    def track_gc(self, owner):
        pass

    def snapshot(self):
        return {}


_NULL_SPAN = contextlib.nullcontext()
NULL_PROFILER = _NullStageProfiler()


# ----------------------------------------------------------------------
# compile-event counters
# ----------------------------------------------------------------------
# Executable constructions per engine builder (lru_cache miss bodies in
# serving/engine.py tick these).  Process-wide on purpose: the compile
# cache being counted is process-wide too.
_BUILDS: Counter = Counter()

# XLA backend compiles seen by the jax.monitoring listener.
_XLA = {"events": 0, "seconds": 0.0, "installed": False}


def count_build(name: str) -> None:
    """Tick the executable-construction counter for a cached builder."""
    _BUILDS[name] += 1


def builder_builds() -> dict[str, int]:
    """Snapshot of builds per cached builder since process start."""
    return dict(_BUILDS)


def _on_event_duration(name: str, secs: float, **kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _XLA["events"] += 1
        _XLA["seconds"] += float(secs)


def install_compile_listener() -> None:
    """Register the jax.monitoring backend-compile listener (idempotent).

    Listener dispatch is a python-list append per *compile*, not per
    call — zero steady-state cost.  Gated gracefully: jax builds
    without ``jax.monitoring`` just leave the counters at zero."""
    if _XLA["installed"]:
        return
    try:
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _XLA["installed"] = True
    except Exception:  # noqa: BLE001 — monitoring API absent/renamed
        pass


def xla_compile_events() -> int:
    """XLA backend compiles observed since the listener was installed."""
    return int(_XLA["events"])


def xla_compile_seconds() -> float:
    return float(_XLA["seconds"])


def compile_counters() -> dict[str, Any]:
    """JSON-ready snapshot of all compile-event counters."""
    return {"builder_builds": builder_builds(),
            "xla_compile_events": xla_compile_events(),
            "xla_compile_seconds": xla_compile_seconds()}


# Installed at import: the engines import this module, and a counter
# that misses the first engine's compiles cannot gate a recompilation
# regression.
install_compile_listener()


# ----------------------------------------------------------------------
# compiled-cost registry
# ----------------------------------------------------------------------
def _xla_cost_analysis(compiled) -> dict[str, float]:
    """XLA's own cost_analysis, normalized to {flops, bytes_accessed}."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — backend without cost analysis
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if "flops" in ca:
        out["xla_flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["xla_bytes_accessed"] = float(ca["bytes accessed"])
    return out


def compiled_cost(name: str, fn: Callable, *args,
                  static_cost_only: bool = False, **kwargs) -> dict:
    """AOT-lower ``fn`` at ``args`` and record its compiled cost.

    Returns {name, compile_s, xla_flops, xla_bytes_accessed (XLA's
    cost_analysis), flops, hbm_bytes (loop-aware hlo_analysis walk),
    peak_live_bytes (largest materialized intermediate), backend}.
    ``fn`` must be a jitted function (has ``.lower``); args may be
    concrete arrays or ``jax.ShapeDtypeStruct``.  This compiles a fresh
    executable (AOT does not share the jit call cache) — call it from
    profiling/bench paths, never the serving loop.
    """
    import jax
    from repro.launch.hlo_analysis import analyze, \
        largest_intermediate_bytes
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **kwargs).compile()
    compile_s = time.perf_counter() - t0
    txt = compiled.as_text()
    walk = analyze(txt, 1)
    rec = {
        "name": name,
        "compile_s": compile_s,
        "flops": walk["flops_per_device"],
        "hbm_bytes": walk["hbm_bytes_per_device"],
        "peak_live_bytes": largest_intermediate_bytes(txt),
        "backend": jax.default_backend(),
    }
    if not static_cost_only:
        rec.update(_xla_cost_analysis(compiled))
    return rec


class CostRegistry:
    """Ordered collection of compiled-cost records for one run."""

    def __init__(self):
        self.records: list[dict] = []

    def record(self, name: str, fn: Callable, *args, **kwargs) -> dict:
        rec = compiled_cost(name, fn, *args, **kwargs)
        self.records.append(rec)
        return rec

    def add(self, rec: dict) -> None:
        self.records.append(rec)

    def to_json(self) -> list[dict]:
        return list(self.records)


# ----------------------------------------------------------------------
# programmatic jax.profiler capture
# ----------------------------------------------------------------------
@contextlib.contextmanager
def trace_capture(log_dir: str | None):
    """Capture an XLA profiler trace into ``log_dir`` (TensorBoard /
    Perfetto-loadable).  ``None`` is a no-op so drivers can pass the
    CLI flag straight through.  A profiler that cannot start raises: a
    run asked to trace must not exit 0 without its trace."""
    if not log_dir:
        yield
        return
    import jax
    from repro.obs.log import get_logger
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        get_logger("prof").info("profiler trace written", dir=log_dir)
