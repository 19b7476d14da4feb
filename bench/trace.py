"""Reduction of a JAX profiler trace to device busy time, per-program and
per-operation device time, and the idle gaps with what the host did.

``collect`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into
plain event tuples; ``reduce`` works on those tuples only, so the self
check can feed it a small recorded trace (``tests/data``).  Device
planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one
event per operation run and the ``XLA Modules`` line one per program.
Op events are named by their HLO instruction (a Pallas kernel by its
``pallas_call``'s function, e.g. ``decision_stats_pallas.7``), program
events by the jitted function (``jit_featurize(<hash>)``).  Host planes
give the spans of what the host was doing (``np.asarray(jax.Array)``,
``PjitFunction(<name>)``, ...).  Both share the trace's clock (ns).
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench_window"


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction; keep the
    instruction's name (``%decision_stats_pallas.7 = ...`` ->
    ``decision_stats_pallas.7``).  Program names stay as they are."""
    return name.split(" = ", 1)[0].lstrip("%")


def collect(log_dir: str) -> dict:
    """{"device": [(dev, line, name, start_ns, dur_ns)], "host": [(line,
    name, start_ns, dur_ns)]} from the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    device, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    device.append((dev, line.name, short_name(ev.name),
                                   float(ev.start_ns), float(ev.duration_ns)))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((line.name, ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"device": device, "host": host}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _window(host):
    """(start, end, line) of the benchmark's window span; the line is the
    Python thread that drove the window."""
    spans = [(s, s + d, line) for line, name, s, d in host
             if name == WINDOW_SPAN]
    if not spans:
        raise RuntimeError(f"trace has no {WINDOW_SPAN!r} span")
    return spans[0]


def _labeller(host, line):
    """f(t) -> name of the innermost span on the driving thread that is
    open at time t ("idle host" when none is)."""
    spans = sorted((s, s + d, name) for ln, name, s, d in host
                   if ln == line and name != WINDOW_SPAN)
    starts = [s for s, _, _ in spans]

    def label(t):
        i = bisect.bisect_right(starts, t) - 1
        for k in range(i, max(i - 256, -1), -1):
            if spans[k][1] >= t:
                return spans[k][2]
        return "idle host"

    return label


def reduce(events: dict, top: int = 10) -> dict:
    """Per-device busy seconds and op and program totals, clipped to the
    ``bench_window`` span, and the busiest device's idle time summed by
    what the driving thread was doing in the middle of each gap."""
    ws, we, host_line = _window(events["host"])
    per_dev = defaultdict(lambda: {"ops": [], "modules": []})
    for dev, line, name, s, d in events["device"]:
        s0, e0 = max(s, ws), min(s + d, we)
        if e0 <= s0:
            continue
        key = "ops" if line == OPS_LINE else "modules"
        per_dev[dev][key].append((name, s0, e0))
    devices = {}
    for dev, ev in sorted(per_dev.items()):
        busy = _merge([(s, e) for _, s, e in ev["ops"]])
        ops, mods = defaultdict(lambda: [0, 0.0]), defaultdict(
            lambda: [0, 0.0])
        for name, s, e in ev["ops"]:
            ops[name][0] += 1
            ops[name][1] += (e - s) * 1e-9
        for name, s, e in ev["modules"]:
            mods[name][0] += 1
            mods[name][1] += (e - s) * 1e-9
        edges = [ws] + [x for iv in busy for x in iv] + [we]
        devices[dev] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "ops": {k: tuple(v) for k, v in ops.items()},
            "modules": {k: tuple(v) for k, v in mods.items()},
            "gaps": [(a, b) for a, b in zip(edges[::2], edges[1::2])
                     if b > a],
        }
    if not devices:
        raise RuntimeError("no device operation ran in the traced window")
    window_s = (we - ws) * 1e-9
    busiest = max(devices, key=lambda d: devices[d]["busy_s"])
    all_ops = defaultdict(float)
    for d in devices.values():
        for name, (_, t) in d["ops"].items():
            all_ops[name] += t
    label = _labeller(events["host"], host_line)
    idle = defaultdict(float)
    for a, b in devices[busiest]["gaps"]:
        idle[label((a + b) / 2)] += (b - a) * 1e-9
    breakdown = {
        "device_ops": sorted(([k, v] for k, v in all_ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }
    return {"window_s": window_s, "devices": devices, "busiest": busiest,
            "busy_s": sum(d["busy_s"] for d in devices.values())
            / len(devices),
            "breakdown": breakdown}


def device_time(reduced: dict, pattern: str, line: str = "ops") -> tuple:
    """(calls, seconds) summed over devices of the ops (or programs)
    whose name contains ``pattern``."""
    n, t = 0, 0.0
    for d in reduced["devices"].values():
        for name, (c, s) in d[line].items():
            if pattern in name:
                n += c
                t += s
    return n, t
