"""Serving benchmark: one cell, one run, one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for.  The cell (``BENCHMARK.json`` ``workloads``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``, driven by ``bench/kinds/<kind>.py``);
every metric is read by ``bench/metrics/<metric>.py``; the configuration's
``"system"`` key names the system under test, ``bench/systems/<system>.py``.

Set-up: the system module builds the engine, its inputs and their feed,
and the traffic's kind warms its shapes.  The window then ticks the
engine for ``--seconds``; a compile inside it is an error.  After the
window the system module checks what was served against its plain
reference.  With ``--trace 1`` the window is traced and the per-layer
metrics are reported; otherwise the end-to-end ones.

A system module is the only interface a new system implements:

* ``CONTROLS``: the names ``--control`` may take for this system.
* ``build(cell, seed, control, phases) -> (system, feed, ctx)``: the
  engine, its inputs and whatever the check needs later (``ctx``, such as
  the weights), all from the seed; each set-up phase's seconds go into
  ``phases`` under a name of its own.
* ``decisions(record) -> int``: how many decisions a retired record
  stands for; the window's decisions are their sum over the records
  retired in it.
* ``compare(cell, ctx, served, feed, due, seed, *, submitted, control,
  log) -> [(name, value, limit, "max" | "min")]``: the numbers compared,
  each with its limit ("max": value <= limit passes).  ``served`` has
  ``r_step`` and ``engines[*].metrics.records``; ``due`` lists the ids
  submitted in the window; ids ``0 .. submitted - 1`` were all sent;
  ``log`` takes one line.

``system`` has ``slots``, ``submit(request)``, ``tick()``, ``pending``,
``n_active``, ``profiler`` (``snapshot()`` of stage counts and seconds),
``engines`` (each with ``metrics.records``, a record having ``rid``,
``admit_s``, ``verdict_s`` and ``n_samples``), ``r_step`` and
``devices()``.  ``feed`` has ``make()`` (the next request) and
``next_rid``.

The last line on standard output is the result; the last lines on
standard error are the numbers compared, each beside its limit.  With
no TPU, or fewer chips than the cell asks for, it exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
SYSTEMS = BENCH / "systems"
GRACE_S = 60.0


class BenchError(RuntimeError):
    """A run that cannot produce a result."""


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, spec: dict | None = None) -> SimpleNamespace:
    """The cell, its configuration, traffic and metrics, by name."""
    spec = spec or _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_file = ROOT / configs[cell["config"]]["file"]
    cfg = _load_json(cfg_file)
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return SimpleNamespace(
        name=name, chips=int(cell["chips"]), config=cell["config"],
        cfg=cfg, traffic=traffic, system=load_system(cfg, cfg_file),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def _module(path: Path, name: str | None = None):
    mod_spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_system(cfg: dict, cfg_file: Path):
    """The module of the system ``cfg`` names, loaded once per file and
    kept as ``bench.systems.<system>``."""
    name = cfg.get("system")
    if not name:
        raise BenchError(f"{cfg_file} names no system (its \"system\" key)")
    path = SYSTEMS / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"{cfg_file} names system {name!r}, but {path} "
                         "does not exist")
    key = f"bench.systems.{name}"
    mod = sys.modules.get(key)
    if mod is None or Path(mod.__file__).resolve() != path.resolve():
        mod = sys.modules[key] = _module(path, key)
    return mod


def reader(metric: str):
    return _module(BENCH / "metrics" / f"{metric}.py").read


def driver(kind: str):
    return _module(BENCH / "kinds" / f"{kind}.py")


def peaks(device_kind: str) -> dict:
    table = _load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} not in bench/peaks.json")
    return table[device_kind]


def _memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class _GcPauses:
    """Python garbage-collector pauses while active (host clock): all
    collections, and the full (generation 2) ones apart."""

    def __init__(self):
        self.count, self.total, self.longest, self._t = 0, 0.0, 0.0, 0.0
        self.full, self.full_total = 0, 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
            return
        dt = time.perf_counter() - self._t
        self.count += 1
        self.total += dt
        self.longest = max(self.longest, dt)
        if info.get("generation") == 2:
            self.full += 1
            self.full_total += dt

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def run_cell(cell: SimpleNamespace, seed: int, seconds: float, trace: bool,
             *, require_tpu: bool = True, control: str | None = None,
             log=print) -> dict:
    """One run of ``cell``; returns the result object."""
    if control is not None and control not in cell.system.CONTROLS:
        raise BenchError(f"control {control!r} is not one of "
                         f"{cell.system.__name__}'s {cell.system.CONTROLS}")
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise BenchError(f"cell needs {cell.chips} chips, found "
                         f"{len(devices)}")
    device_peaks = peaks(devices[0].device_kind) if require_tpu else None
    phases = {"start": time.perf_counter() - T_START}
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import prof
    enable_compile_cache()
    traffic = cell.traffic
    kind = driver(traffic["kind"])

    system, feed, ctx = cell.system.build(cell, seed, control, phases)
    t = time.perf_counter()
    kind.warmup(system, feed, traffic)
    phases["warmup"] = time.perf_counter() - t
    compiles = prof.xla_compile_events()
    setup_s = time.perf_counter() - T_START
    log("setup " + " ".join(f"{k}={v:.3f}s" for k, v in phases.items())
        + f" compiles={compiles}", file=sys.stderr)

    stages0 = system.profiler.snapshot()
    trace_dir = None
    if trace:
        from bench import trace as tr
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    pauses = _GcPauses()
    with jax.profiler.TraceAnnotation("bench_window"), pauses:
        win = kind.window(system, feed, traffic, seconds, seed)
    if trace:
        jax.profiler.stop_trace()
    if prof.xla_compile_events() != compiles:
        raise BenchError(f"{prof.xla_compile_events() - compiles} compiles "
                         "inside the measured window")
    stages = {k: (v["count"] - stages0.get(k, {}).get("count", 0),
                  v["total_s"] - stages0.get(k, {}).get("total_s", 0.0))
              for k, v in system.profiler.snapshot().items()}
    t0, t1 = win["t0"], win["t1"]
    records = [r for e in system.engines for r in e.metrics.records]
    due = [rid for rid, _ in win["due"]]
    log(f"gc collections={pauses.count} pause_s={pauses.total:.4f} "
        f"max_pause_s={pauses.longest:.4f} full={pauses.full} "
        f"full_pause_s={pauses.full_total:.4f} ticks={win['ticks']}",
        file=sys.stderr)
    # serve what is still in flight (answers due in the window are waited
    # for up to a minute), so every admission batch is complete
    while (system.pending or system.n_active) and (
            time.perf_counter() < t1 + GRACE_S):
        system.tick()
    memory_peak = _memory_peak(system.devices())
    by_rid = {r.rid: r for e in system.engines for r in e.metrics.records}
    run = SimpleNamespace(
        cell=cell, seed=seed, setup_s=setup_s, t0=t0, t1=t1,
        window_s=t1 - t0, ticks=win["ticks"], stages=stages,
        decisions=sum(cell.system.decisions(r) for r in records
                      if t0 <= r.verdict_s <= t1),
        due=win["due"], records=by_rid,
        chips=len(system.devices()), peaks=device_peaks, cfg=cell.cfg,
        r_step=system.r_step, trace=None)
    if trace:
        run.trace = tr.reduce(tr.collect(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # what was served, apart from the engine, whose device state is freed
    # before the reference runs
    served = SimpleNamespace(r_step=system.r_step, engines=[
        SimpleNamespace(metrics=SimpleNamespace(records=e.metrics.records))
        for e in system.engines])
    del system
    compared = cell.system.compare(
        cell, ctx, served, feed, due, seed, submitted=feed.next_rid,
        control=control, log=lambda m: log(m, file=sys.stderr))
    correct = all((v <= lim) if kind_ == "max" else (v >= lim)
                  for _, v, lim, kind_ in compared)
    checks = {name: {"value": v, "limit": lim,
                     "pass": "<=" if kind_ == "max" else ">="}
              for name, v, lim, kind_ in compared}
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['pass']} "
            f"{c['limit']!r})", file=sys.stderr)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": run.chips, "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": len(due),
              "failed": int(sum(1 for rid in due if rid not in by_rid)),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control",
                    help="serve one of the system's controls (its module's "
                         "CONTROLS) instead of the configuration (for "
                         "measuring limits)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    try:
        cell = load_cell(args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          control=args.control)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
