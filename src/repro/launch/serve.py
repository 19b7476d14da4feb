"""Serving driver: a thin CLI over the continuous-batching engine.

The old driver here was a single-batch sequential loop spending a fixed
R = 20 GRNG samples on every input.  It is replaced by the
``repro.serving`` subsystem: fixed decode slots, an admission queue,
mid-batch retirement, and an adaptive-fidelity controller that starts
every decision at a small R and escalates only while the accept/flag
triage is statistically ambiguous (paper Fig. 1).

Two workload modes:

  * LM archs (``--arch qwen3-0.6b`` etc.): continuous-batching token
    decode; each token decision is triaged, flagged requests retire to
    the verification queue.
  * ``--arch sar_cnn``: the paper's aerial search-and-rescue stream —
    synthetic SARD image patches (data/sard.py), optionally with a
    corrupted fraction (fog/frost/motion/snow), classified through the
    Bayesian-head CNN with per-slot escalation depths.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --slots 4 --prompt-len 16 --gen 8 --requests 16 [--fixed]
  PYTHONPATH=src python -m repro.launch.serve --arch sar_cnn \
      --requests 128 --corrupt-frac 0.25 --corruption fog

Multi-device note: wrap engine construction + run in
``jax.set_mesh(make_debug_mesh())`` and pass a mesh-hinted config to
shard the pool batch across 'data' — the engine's jitted pool updates
are ordinary jit calls and follow the ambient mesh.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.core.energy import LayerShape
from repro.data.tokens import TokenPipelineConfig, batch_at, stub_frames, \
    stub_image_embeds
from repro.obs.log import get_logger
from repro.obs.telemetry import TelemetryConfig
from repro.serving import (LMServingEngine, Request, SarServingEngine,
                           ServingMetrics, TriagePolicy)

log = get_logger("serve")


def _open_loop_offsets(arrival, n: int, seed: int):
    """Resolve an ``--arrival`` spec (string or ArrivalSpec) into the
    parsed spec + its [n] seeded offsets."""
    from repro.serving.load import ArrivalSpec
    spec = (ArrivalSpec.parse(arrival) if isinstance(arrival, str)
            else arrival)
    return spec, spec.offsets(n, seed=seed)


def collect_alerts(out: dict, source: str):
    """Run the unified alert bus over a finished serve summary: drift
    advisories, lifetime heal events, SLO burn breaches, and fleet
    backpressure saturation become one typed advisory stream (logged as
    they are emitted; attached as ``out["alerts"]`` when non-empty)."""
    from repro.obs.alerts import AlertBus
    bus = AlertBus()
    bus.observe_drift(out.get("drift"), source=source)
    for ev in (out.get("lifetime") or {}).get("events", []):
        bus.observe_heal(ev, source=source)
    bus.observe_slo(out.get("slo"), source=source)
    bus.observe_backpressure(out.get("slo"), source=source)
    if bus.advisories:
        out["alerts"] = bus.to_json()
    return bus


def lm_layer_shapes(cfg) -> list:
    """Analytic energy layers: d_model-square trunk approximation + the
    Bayesian vocab head (the R-sampled part)."""
    shapes = [LayerShape(cfg.d_model, cfg.d_model)] * (4 * cfg.n_layers)
    shapes.append(LayerShape(cfg.d_model, cfg.vocab_padded, bayesian=True))
    return shapes


def sar_layer_shapes(cfg) -> list:
    shapes, c_in = [], 1
    for c_out in cfg.channels:
        shapes.append(LayerShape(cfg.kernel**2 * c_in, c_out))
        c_in = c_out
    shapes.append(LayerShape(cfg.channels[-1], cfg.n_classes, bayesian=True))
    return shapes


def serve(arch: str, *, smoke: bool = True, batch: int = 4,
          prompt_len: int = 16, gen_len: int = 8, n_requests: int | None = None,
          adaptive: bool = True, policy: TriagePolicy | None = None,
          seed: int = 0, cache_margin: int = 4, fused: bool = True,
          telemetry: bool | TelemetryConfig = True,
          tracer=None, profiler=True,
          cost_records: bool = False) -> dict:
    """LM serving through the engine. ``batch`` is the slot count.

    ``fused``: run escalation rounds through the fused Pallas decision
    kernel (kernels/decision_kernel.py — no [R, B, V] materialization);
    False selects the materializing ``mix_samples → update_stats``
    path (verdict-identical).

    ``telemetry``/``tracer``: obs/ device-resident telemetry (snapshot
    under out["telemetry"]) and per-request span tracing."""
    cfg = get_config(arch, smoke=smoke)
    n_requests = n_requests or 2 * batch
    policy = policy or TriagePolicy()
    pipe = TokenPipelineConfig(vocab=cfg.vocab, seq_len=prompt_len,
                               global_batch=batch, seed=seed)
    extras = {}
    if cfg.family == "audio":
        extras["frames"] = stub_frames(pipe, cfg.n_frames, cfg.d_model, 0,
                                       batch)
    if cfg.family == "vlm":
        extras["image_embeds"] = stub_image_embeds(
            pipe, cfg.n_image_tokens, cfg.d_model, 0, batch)

    cache_len = prompt_len + gen_len * (1 + cache_margin)
    if cfg.swa_window is not None:
        # Rolling caches don't support mid-stream admission (engine
        # guard); stay within the window.  Oversized prompt+gen then
        # fails loudly in the engine's capacity check.
        cache_len = min(cache_len, cfg.swa_window)
    from repro.hw import compile_network
    layers = lm_layer_shapes(cfg)
    metrics = ServingMetrics(layers=layers,
                             tile_program=compile_network(layers))
    engine = LMServingEngine(
        jax_params_init(cfg, seed), cfg, n_slots=batch,
        prompt_len=prompt_len, cache_len=cache_len, policy=policy,
        adaptive_mode=adaptive, metrics=metrics, extras=extras,
        fused=fused, telemetry=telemetry, tracer=tracer,
        profiler=profiler)

    rid = 0
    t0 = time.perf_counter()
    for step in range((n_requests + batch - 1) // batch):
        prompts = np.asarray(batch_at(pipe, step)["tokens"])
        for i in range(min(batch, n_requests - rid)):
            engine.submit(Request(rid=rid, payload=prompts[i],
                                  arrival_s=time.time(),
                                  max_new_tokens=gen_len))
            rid += 1
    out = engine.run()
    out["wall_s"] = time.perf_counter() - t0
    out["tokens_per_s"] = out["decisions"] / out["wall_s"]
    out["host_syncs"] = engine.host_syncs
    if cost_records:
        out["compiled_costs"] = engine.compiled_cost_records()
    out["flagged_fraction"] = out.get("flag_fraction", float("nan"))
    out["verdicts"] = [
        {"rid": r.rid, "verdict": r.verdict, "confidence": r.confidence,
         "mutual_information": r.mutual_information,
         "n_samples": r.n_samples, "n_tokens": r.n_decisions}
        for r in metrics.records]
    return out


def jax_params_init(cfg, seed: int):
    from repro.models.registry import get_api
    return get_api(cfg).init(jax.random.PRNGKey(seed), cfg)


def make_sar_stream(n_requests: int, *, corrupt_frac: float = 0.0,
                    corruption: str = "fog", severity: float = 1.0,
                    image_size: int = 32, seed: int = 7, batch: int = 32,
                    step0: int = 1000) -> list:
    """Request stream over synthetic SARD, with a corrupted tail mixed in.

    step0 offsets past the training stream so serving never sees
    training images.  Returns a list of Requests with
    ``meta={'corrupted': bool, 'label': int}``.
    """
    from repro.data.sard import SardConfig, batch_at as sard_batch, \
        corrupted_batch
    dcfg = SardConfig(image_size=image_size, seed=seed)
    reqs, rid = [], 0
    n_batches = (n_requests + batch - 1) // batch
    for b in range(n_batches):
        clean = sard_batch(dcfg, step0 + b, batch)
        dirty = corrupted_batch(dcfg, step0 + b, batch, corruption, severity)
        n_dirty = int(round(batch * corrupt_frac))
        for i in range(min(batch, n_requests - rid)):
            corrupted = i < n_dirty
            img = (dirty if corrupted else clean)["images"][i]
            reqs.append(Request(
                rid=rid, payload=np.asarray(img), arrival_s=time.time(),
                meta={"corrupted": corrupted,
                      "label": int(clean["labels"][i])}))
            rid += 1
    return reqs


def serve_sar(*, n_requests: int = 128, n_slots: int = 32,
              adaptive: bool = True, policy: TriagePolicy | None = None,
              corrupt_frac: float = 0.0, corruption: str = "fog",
              params=None, cfg=None, seed: int = 0,
              chip_instance=None, calibrated: bool = True,
              slot_axis: str | None = None, fused: bool = True,
              telemetry: bool | TelemetryConfig = True,
              tracer=None, profiler=True, slo=(),
              arrival=None, cost_records: bool = False) -> dict:
    """SAR image-stream serving. Untrained params unless provided.

    ``slo``: SLO spec strings (``"0.25:p99"``) the time-to-verdict
    tracker evaluates — attainment/burn-rate land in ``out["slo"]``.
    ``arrival``: an ``--arrival`` spec (``"poisson:8"`` etc.) — the
    stream is then driven OPEN-LOOP by serving/load.py on a seeded
    arrival schedule instead of being enqueued all at once, so queue
    wait and time-to-verdict measure a real traffic regime.

    ``chip_instance``: a hw.ChipInstance (or an int seed — one chip is
    sampled from the default VariationSpec) — the engine then serves
    *fully* on that die's digital twin: the conv trunk through the
    nonideal CIM kernel (per-column ADC gain/offset + programming
    noise), the Bayesian head on the degraded GRNG with per-chip
    constants; ``calibrated`` selects the per-instance recalibrated
    head (hw/calib.py) vs the golden factory transform.  The summary
    gains chip metadata; energy/area accounting is tilemap-true (placed
    blocks + utilization from the tile compiler) with or without a
    chip.
    """
    from repro.hw import compile_network
    from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
    cfg = cfg or SarCnnConfig()
    if params is None:
        params = init_sar_cnn(jax.random.PRNGKey(3 + seed), cfg)
    policy = policy or TriagePolicy(conf_threshold=0.7, mi_threshold=0.05)
    layers = sar_layer_shapes(cfg)
    program = compile_network(layers)
    head = hcfg = None
    extra = {}
    if chip_instance is not None:
        from repro.core.bayes_layer import sigma_of
        from repro.core.sampling import BayesHeadConfig
        from repro.hw import prepare_instance_head, sample_instances
        if not hasattr(chip_instance, "grng"):
            chip_instance = sample_instances(int(chip_instance), 1)[0]
        base_hcfg = BayesHeadConfig(
            num_samples=policy.r_max, mode="rank16", grng=cfg.grng,
            compute_dtype=jnp.float32, hoist_basis=True)
        head, hcfg = prepare_instance_head(
            params["head"]["mu"], sigma_of(params["head"]), base_hcfg,
            chip_instance, calibrated=calibrated)
        extra = {
            "chip_id": chip_instance.chip_id,
            "chip_device_seed": chip_instance.device_seed,
            "chip_read_sigma": chip_instance.read_sigma,
            "chip_temp_c": chip_instance.temp_c,
            "calibrated": bool(calibrated),
        }
    metrics = ServingMetrics(layers=layers, extra=extra,
                             tile_program=program)
    from repro.obs.slo import SloTracker
    slo_tracker = SloTracker(slos=tuple(slo)) if slo else True
    engine = SarServingEngine(params, cfg, n_slots=n_slots, policy=policy,
                              adaptive_mode=adaptive, metrics=metrics,
                              head=head, hcfg=hcfg, chip=chip_instance,
                              slot_axis=slot_axis, fused=fused,
                              telemetry=telemetry, tracer=tracer,
                              profiler=profiler, slo=slo_tracker)
    reqs = make_sar_stream(n_requests, corrupt_frac=corrupt_frac,
                           corruption=corruption,
                           image_size=cfg.image_size)
    t0 = time.perf_counter()
    if arrival is not None:
        from repro.serving.load import run_open_loop
        spec, offsets = _open_loop_offsets(arrival, len(reqs), seed)
        out = run_open_loop(engine, reqs, offsets)
        out["arrival"] = spec.to_dict()
    else:
        for r in reqs:
            engine.submit(r)
        out = engine.run()
    out["wall_s"] = time.perf_counter() - t0
    if slo:
        # engine shares the caller-built tracker (so the SLO specs ride
        # along) — attach its snapshot here
        out["slo"] = slo_tracker.snapshot()
    out["host_syncs"] = engine.host_syncs
    out["host_syncs_per_decision"] = (engine.host_syncs
                                      / max(out["decisions"], 1))
    if cost_records:
        # AOT compiled-cost capture of the live hot functions —
        # profiling path only (compiles fresh executables).
        out["compiled_costs"] = engine.compiled_cost_records()
    out["flagged_fraction"] = out.get("flag_fraction", float("nan"))
    out["verdicts"] = [
        {"rid": r.rid, "verdict": r.verdict, "confidence": r.confidence,
         "mutual_information": r.mutual_information,
         "n_samples": r.n_samples} for r in metrics.records]
    if engine.tcfg is not None and out.get("telemetry"):
        # Online drift check against the deployment's calibration-time
        # belief: the measured instance config when calibrated, the
        # golden factory config otherwise (obs/drift docstring).
        from repro.obs.drift import drift_status, reference_for
        ref = reference_for(cfg, engine.hcfg,
                            calibrated=(chip_instance is not None
                                        and calibrated),
                            probe_cells=engine.tcfg.probe_cells)
        out["drift"] = drift_status(out["telemetry"], ref).to_dict()
        if out["drift"]["advisory"]:
            log.warning(out["drift"]["advisory"])
    collect_alerts(out, "serve_sar")
    return out


def serve_sar_fleet(*, n_requests: int = 256, n_pools: int = 4,
                    slots_per_pool: int = 32, adaptive: bool = True,
                    policy: TriagePolicy | None = None,
                    corrupt_frac: float = 0.0, corruption: str = "fog",
                    params=None, cfg=None, seed: int = 0,
                    chip_instance=None, calibrated: bool = True,
                    fused: bool = True, gang: bool | None = None,
                    queue_cap: int | None = None,
                    telemetry: bool | TelemetryConfig = True,
                    tracer=None, profiler=True, slo=(),
                    arrival=None) -> dict:
    """Mesh-of-pools SAR serving (serving/fleet.py).

    ``tracer``: a shared obs.trace.Tracer — the fleet stitches router
    tick spans (pid 0) and per-pool dispatch/slot tracks (pid p+1) into
    ONE Chrome/Perfetto timeline, with flow arrows router → slot per
    request.  ``slo``/``arrival``: as in :func:`serve_sar` (the SLO
    tracker is fleet-wide: one snapshot covering router latency, queue
    depths, and backpressure).

    ``n_pools`` complete serving pools tiled over a 1-D ``("pool",)``
    device mesh behind a least-loaded admission router; each fleet tick
    runs ONE shard_map'd gang round for every pool (``gang=None``
    auto-enables it when the process has >= n_pools devices; on CPU,
    set XLA_FLAGS=--xla_force_host_platform_device_count=N before the
    process starts).  Verdicts are bit-identical to
    ``serve_sar`` pools fed the same admission sequences; the summary
    is the exact sum of the per-pool reports (energy, telemetry,
    decisions) plus router stats (``routed_per_pool``,
    ``backlog_peak``).

    ``chip_instance``/``calibrated``: as in ``serve_sar`` — every pool
    serves the same die's digital twin.
    """
    from repro.hw import compile_network
    from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
    from repro.serving import SarServingFleet
    cfg = cfg or SarCnnConfig()
    if params is None:
        params = init_sar_cnn(jax.random.PRNGKey(3 + seed), cfg)
    policy = policy or TriagePolicy(conf_threshold=0.7, mi_threshold=0.05)
    layers = sar_layer_shapes(cfg)
    program = compile_network(layers)
    head = hcfg = None
    if chip_instance is not None:
        from repro.core.bayes_layer import sigma_of
        from repro.core.sampling import BayesHeadConfig
        from repro.hw import prepare_instance_head, sample_instances
        if not hasattr(chip_instance, "grng"):
            chip_instance = sample_instances(int(chip_instance), 1)[0]
        base_hcfg = BayesHeadConfig(
            num_samples=policy.r_max, mode="rank16", grng=cfg.grng,
            compute_dtype=jnp.float32, hoist_basis=True)
        head, hcfg = prepare_instance_head(
            params["head"]["mu"], sigma_of(params["head"]), base_hcfg,
            chip_instance, calibrated=calibrated)
    from repro.obs.slo import SloTracker
    slo_tracker = SloTracker(slos=tuple(slo)) if slo else True
    fleet = SarServingFleet(
        params, cfg, n_pools=n_pools, slots_per_pool=slots_per_pool,
        policy=policy, adaptive_mode=adaptive, head=head, hcfg=hcfg,
        chip=chip_instance, fused=fused, telemetry=telemetry,
        layers=layers, tile_program=program, queue_cap=queue_cap,
        gang=gang, tracer=tracer, profiler=profiler, slo=slo_tracker)
    reqs = make_sar_stream(n_requests, corrupt_frac=corrupt_frac,
                           corruption=corruption,
                           image_size=cfg.image_size)
    if arrival is not None:
        from repro.serving.load import run_open_loop
        spec, offsets = _open_loop_offsets(arrival, len(reqs), seed)
        out = run_open_loop(fleet, reqs, offsets)
        out["arrival"] = spec.to_dict()
    else:
        for r in reqs:
            fleet.submit(r)
        out = fleet.run()
    if chip_instance is not None:
        out["chip_id"] = chip_instance.chip_id
        out["chip_device_seed"] = chip_instance.device_seed
        out["calibrated"] = bool(calibrated)
    out["flagged_fraction"] = out.get("flag_fraction", float("nan"))
    out["verdicts"] = [
        {"rid": r.rid, "pool": fleet.routes.get(r.rid),
         "verdict": r.verdict, "confidence": r.confidence,
         "mutual_information": r.mutual_information,
         "n_samples": r.n_samples}
        for eng in fleet.engines for r in eng.metrics.records]
    out["verdicts"].sort(key=lambda v: v["rid"])
    collect_alerts(out, "serve_sar_fleet")
    return out


def serve_sar_lifetime(*, lifetime, chip_instance,
                       n_requests: int = 128, n_slots: int = 32,
                       adaptive: bool = True,
                       policy: TriagePolicy | None = None,
                       corrupt_frac: float = 0.0, corruption: str = "fog",
                       params=None, cfg=None, seed: int = 0,
                       calibrated: bool = True, fused: bool = True,
                       telemetry: bool | TelemetryConfig = True,
                       tracer=None, profiler=True) -> dict:
    """SAR serving across a die's LIFETIME: the stream is cut into
    ``lifetime.epochs`` segments, the die ages ``lifetime.age_rate``
    simulated field-seconds per decision, and (with
    ``auto_recalibrate``) drift advisories from the streamed telemetry
    trigger an in-place recalibrate-and-hot-swap between segments
    (hw/redeploy.SelfHealingController + SarServingEngine.swap_head).

    With ``lifetime.active`` False this IS ``serve_sar`` — one segment,
    no controller, bit-identical verdicts and host-sync counts — so
    callers can pass a lifetime config unconditionally.

    Returns the usual serve summary plus ``out["lifetime"]``: age, heal
    events, advisory count, and the final drift status.
    """
    from repro.core.bayes_layer import sigma_of
    from repro.core.sampling import BayesHeadConfig
    from repro.hw import compile_network, sample_instances
    from repro.hw.redeploy import SelfHealingController
    from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
    if not lifetime.active:
        out = serve_sar(n_requests=n_requests, n_slots=n_slots,
                        adaptive=adaptive, policy=policy,
                        corrupt_frac=corrupt_frac, corruption=corruption,
                        params=params, cfg=cfg, seed=seed,
                        chip_instance=chip_instance, calibrated=calibrated,
                        fused=fused, telemetry=telemetry, tracer=tracer,
                        profiler=profiler)
        out["lifetime"] = {"active": False, "age_s": 0.0, "heals": 0,
                           "advisories": 0, "epochs": 1}
        return out
    if chip_instance is None:
        raise ValueError("lifetime serving ages a specific die — pass "
                         "chip_instance (a ChipInstance or an int seed)")
    if telemetry is False:
        raise ValueError("lifetime serving watches drift through the "
                         "device-resident telemetry probe — telemetry "
                         "must stay enabled")
    if not hasattr(chip_instance, "grng"):
        chip_instance = sample_instances(int(chip_instance), 1)[0]
    cfg = cfg or SarCnnConfig()
    if params is None:
        params = init_sar_cnn(jax.random.PRNGKey(3 + seed), cfg)
    policy = policy or TriagePolicy(conf_threshold=0.7, mi_threshold=0.05)
    base_hcfg = BayesHeadConfig(
        num_samples=policy.r_max, mode="rank16", grng=cfg.grng,
        compute_dtype=jnp.float32, hoist_basis=True)
    tcfg = telemetry if isinstance(telemetry, TelemetryConfig) \
        else TelemetryConfig()
    ctl = SelfHealingController(
        chip_instance, params["head"]["mu"], sigma_of(params["head"]),
        base_hcfg, calibrated=calibrated, spec=lifetime.spec,
        gate=lifetime.gate, probe_cells=tcfg.probe_cells)
    layers = sar_layer_shapes(cfg)
    metrics = ServingMetrics(
        layers=layers, tile_program=compile_network(layers),
        extra={"chip_id": chip_instance.chip_id,
               "chip_device_seed": chip_instance.device_seed,
               "calibrated": bool(calibrated)})
    engine = SarServingEngine(params, cfg, n_slots=n_slots, policy=policy,
                              adaptive_mode=adaptive, metrics=metrics,
                              head=ctl.head, hcfg=ctl.hcfg,
                              chip=chip_instance, fused=fused,
                              telemetry=tcfg, tracer=tracer,
                              profiler=profiler)
    reqs = make_sar_stream(n_requests, corrupt_frac=corrupt_frac,
                           corruption=corruption,
                           image_size=cfg.image_size)
    epochs = max(1, int(lifetime.epochs))
    seg = -(-len(reqs) // epochs)
    served, advisories = 0, 0
    t0 = time.perf_counter()
    for k in range(epochs):
        chunk = reqs[k * seg:(k + 1) * seg]
        if not chunk:
            break
        if k:
            # Drift ARRIVES mid-stream: the die moves to the age its
            # decision count implies and the engine serves the stale
            # belief on the aged physics (telemetry probe included).
            head, hcfg = ctl.advance(lifetime.age_rate * served)
            engine.swap_head(head, hcfg)
        for r in chunk:
            engine.submit(r)
        out = engine.run()
        served += len(chunk)
        status = ctl.observe_snapshot(engine.telemetry_snapshot())
        if status.drifted:
            advisories += 1
            log.warning(status.advisory)
        if lifetime.auto_recalibrate and status.drifted:
            ev = ctl.heal(status)
            engine.swap_head(*ctl.view())
            log.info("healed", age_s=ev.age_s, calib_epoch=ev.calib_epoch,
                     z_mean=round(ev.z_mean, 2), z_std=round(ev.z_std, 2))
    out["wall_s"] = time.perf_counter() - t0
    out["host_syncs"] = engine.host_syncs
    out["host_syncs_per_decision"] = (engine.host_syncs
                                      / max(out["decisions"], 1))
    out["flagged_fraction"] = out.get("flag_fraction", float("nan"))
    out["verdicts"] = [
        {"rid": r.rid, "verdict": r.verdict, "confidence": r.confidence,
         "mutual_information": r.mutual_information,
         "n_samples": r.n_samples} for r in metrics.records]
    out["lifetime"] = dict(ctl.report(), active=True, epochs=epochs,
                           advisories=advisories,
                           age_rate=lifetime.age_rate,
                           auto_recalibrate=lifetime.auto_recalibrate)
    collect_alerts(out, "serve_sar_lifetime")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=tuple(ARCHS) + ("sar_cnn",),
                    required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots (default: 4 for LM, 32 for sar_cnn)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    ap.add_argument("--fixed", action="store_true",
                    help="fixed R=r_max per decision (paper baseline)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    default=True,
                    help="disable the fused Pallas decision kernel and "
                         "use the materializing mix_samples → "
                         "update_stats path (verdict-identical)")
    ap.add_argument("--conf-threshold", type=float, default=0.8)
    ap.add_argument("--mi-threshold", type=float, default=0.5)
    ap.add_argument("--r-min", type=int, default=4)
    ap.add_argument("--r-max", type=int, default=20)
    ap.add_argument("--pools", type=int, default=None,
                    help="sar_cnn only: serve through the mesh-of-pools "
                         "fleet with this many engine pools "
                         "(serving/fleet.py; one shard_map'd gang "
                         "dispatch per tick when devices allow)")
    ap.add_argument("--slots-per-pool", type=int, default=32,
                    help="decode slots per fleet pool (with --pools)")
    ap.add_argument("--corrupt-frac", type=float, default=0.0)
    ap.add_argument("--corruption", default="fog",
                    choices=("fog", "frost", "motion", "snow"))
    ap.add_argument("--chip-instance", type=int, default=None,
                    help="serve on a sampled FeFET chip instance "
                         "(hw/ digital twin) drawn with this seed")
    ap.add_argument("--chip-severity", type=float, default=1.0,
                    help="variation severity multiplier for the "
                         "sampled chip")
    ap.add_argument("--uncalibrated", action="store_true",
                    help="skip per-instance recalibration (golden "
                         "factory transform on the degraded chip)")
    ap.add_argument("--age-rate", type=float, default=0.0,
                    help="simulated field-seconds of FeFET aging per "
                         "decision (hw/aging.py); 0 disables the "
                         "lifetime loop (exact pre-lifetime path)")
    ap.add_argument("--age-epochs", type=int, default=4,
                    help="age/heal checkpoints the stream is cut into")
    ap.add_argument("--auto-recalibrate", action="store_true",
                    help="act on drift advisories: recalibrate the aged "
                         "die and hot-swap the healed head mid-stream "
                         "(hw/redeploy.py)")
    ap.add_argument("--no-telemetry", dest="telemetry",
                    action="store_false", default=True,
                    help="disable the device-resident obs/ telemetry "
                         "(compiles the exact pre-telemetry graph)")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace JSON of the "
                         "run's request spans to PATH (with --pools: "
                         "ONE stitched fleet timeline — router ticks, "
                         "per-pool gang-dispatch tracks, and request "
                         "flow arrows router -> pool -> slot)")
    ap.add_argument("--arrival", type=str, default=None, metavar="SPEC",
                    help="sar_cnn: drive serving OPEN-LOOP on a seeded "
                         "arrival schedule instead of enqueueing "
                         "everything up front — poisson:RATE, "
                         "burst:RATE[:FACTOR], or ramp:LO:HI (req/s)")
    ap.add_argument("--slo", action="append", default=None,
                    metavar="TARGET:PCT[:BURN]",
                    help="time-to-verdict SLO, e.g. 0.25:p99 — "
                         "repeatable; attainment and error-budget burn "
                         "rate land in the summary, breaches on the "
                         "alert bus")
    ap.add_argument("--metrics-out", type=str, default=None,
                    metavar="PREFIX",
                    help="write PREFIX.prom (Prometheus text) and "
                         "PREFIX.json with the run's metrics + "
                         "telemetry snapshot")
    ap.add_argument("--profile", type=str, default=None, metavar="DIR",
                    help="capture a jax.profiler (XLA) trace of the "
                         "whole run into DIR (TensorBoard-loadable) "
                         "and record compiled-cost analyses of the "
                         "engine's hot functions")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    policy = TriagePolicy(conf_threshold=args.conf_threshold,
                          mi_threshold=args.mi_threshold,
                          r_min=args.r_min, r_max=args.r_max)

    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer
        tracer = Tracer("repro-serving")

    from repro.obs.prof import trace_capture
    if args.arch == "sar_cnn":
        chip = None
        if args.chip_instance is not None:
            from repro.hw import VariationSpec, sample_instances
            chip = sample_instances(
                args.chip_instance, 1,
                VariationSpec().scaled(args.chip_severity))[0]
        with trace_capture(args.profile):
            if args.pools:
                out = serve_sar_fleet(
                    n_requests=args.requests or 128,
                    n_pools=args.pools,
                    slots_per_pool=args.slots_per_pool,
                    adaptive=not args.fixed, policy=policy,
                    corrupt_frac=args.corrupt_frac,
                    corruption=args.corruption, chip_instance=chip,
                    calibrated=not args.uncalibrated, fused=args.fused,
                    telemetry=args.telemetry, tracer=tracer,
                    slo=tuple(args.slo or ()), arrival=args.arrival)
                log.info("fleet", pools=out["n_pools"],
                         gang=out["gang"],
                         routed=out["routed_per_pool"],
                         backlog_peak=out["backlog_peak"],
                         host_syncs_per_decision=round(
                             out["host_syncs_per_decision"], 4))
            elif args.age_rate > 0.0 or args.auto_recalibrate:
                from repro.hw.redeploy import LifetimeConfig
                out = serve_sar_lifetime(
                    lifetime=LifetimeConfig(
                        age_rate=args.age_rate, epochs=args.age_epochs,
                        auto_recalibrate=args.auto_recalibrate),
                    chip_instance=chip, n_requests=args.requests or 128,
                    n_slots=args.slots or 32, adaptive=not args.fixed,
                    policy=policy, corrupt_frac=args.corrupt_frac,
                    corruption=args.corruption,
                    calibrated=not args.uncalibrated, fused=args.fused,
                    telemetry=args.telemetry, tracer=tracer)
                lt = out["lifetime"]
                log.info("lifetime", age_s=lt.get("age_s", 0.0),
                         advisories=lt.get("advisories", 0),
                         heals=lt.get("heals", 0),
                         calib_epoch=lt.get("calib_epoch", 0))
            else:
                out = serve_sar(n_requests=args.requests or 128,
                                n_slots=args.slots or 32,
                                adaptive=not args.fixed, policy=policy,
                                corrupt_frac=args.corrupt_frac,
                                corruption=args.corruption,
                                chip_instance=chip,
                                calibrated=not args.uncalibrated,
                                fused=args.fused,
                                telemetry=args.telemetry,
                                tracer=tracer,
                                slo=tuple(args.slo or ()),
                                arrival=args.arrival,
                                cost_records=bool(args.profile))
        chip_note = ""
        if chip is not None and "tile_area_mm2" in out:
            chip_note = (f" [chip seed={args.chip_instance} "
                         f"T={chip.temp_c:.0f}C "
                         f"{'cal' if not args.uncalibrated else 'UNCAL'} "
                         f"area={out['tile_area_mm2']:.2f}mm2 "
                         f"util={out['tile_utilization']:.2f}]")
        grng_note = ""
        if "grng_energy_per_decision_aJ" in out:
            grng_note = (f"; GRNG "
                         f"{out['grng_energy_per_decision_aJ']:.0f} "
                         f"aJ/decision")
        log.info(
            f"[sar] {out['decisions']} decisions in "
            f"{out['wall_s']:.2f}s ({out['decisions_per_s']:.1f}/s); "
            f"mean samples/decision "
            f"{out.get('mean_samples_per_decision', float('nan')):.1f}; "
            f"{100*out['flagged_fraction']:.1f}% flagged"
            + grng_note + chip_note)
        if out.get("drift"):
            log.info("drift", drifted=out["drift"]["drifted"],
                     z_mean=round(out["drift"]["z_mean"], 2),
                     z_std=round(out["drift"]["z_std"], 2))
        if out.get("slo"):
            snap = out["slo"]
            log.info("slo", p50_s=round(snap["p50_s"], 4),
                     p95_s=round(snap["p95_s"], 4),
                     p99_s=round(snap["p99_s"], 4),
                     queue_wait_share=round(
                         snap.get("queue_wait_share", float("nan")), 3))
            for s in snap.get("slos", []):
                log.info("slo target", name=s["name"],
                         attainment=round(s["attainment"], 4),
                         burn_rate=round(s["burn_rate"], 2),
                         breach=s["breach"])
    else:
        with trace_capture(args.profile):
            out = serve(args.arch, smoke=args.smoke,
                        batch=args.slots or 4,
                        prompt_len=args.prompt_len, gen_len=args.gen,
                        n_requests=args.requests,
                        adaptive=not args.fixed,
                        policy=policy, fused=args.fused,
                        telemetry=args.telemetry, tracer=tracer,
                        cost_records=bool(args.profile))
        log.info(
            f"{out['requests']} requests / {out['decisions']} "
            f"tokens in {out['wall_s']:.2f}s "
            f"({out['tokens_per_s']:.1f} tok/s); mean samples/token "
            f"{out['mean_samples_per_decision']:.1f}; "
            f"{100*out['flagged_fraction']:.1f}% flagged for verification")

    if tracer is not None:
        import os
        d = os.path.dirname(args.trace)
        if d:
            os.makedirs(d, exist_ok=True)
        tracer.export(args.trace)
        log.info("trace written", path=args.trace,
                 events=len(tracer.events))
    if args.metrics_out:
        from repro.obs.registry import serving_registry
        reg = serving_registry(
            {k: v for k, v in out.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)},
            telemetry=out.get("telemetry"), drift=out.get("drift"),
            profile=out.get("stage_profile"),
            compile_counters=out.get("compile_counters"),
            compiled_costs=out.get("compiled_costs"),
            slo=out.get("slo"), alerts=out.get("alerts"),
            arch=args.arch)
        prom, js = reg.write(args.metrics_out)
        log.info("metrics written", prom=prom, json=js)


if __name__ == "__main__":
    main()
