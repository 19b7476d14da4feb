"""Mission driver: fly the closed-loop SAR simulator from the CLI.

Wraps repro/mission: builds the world + fleet from flags, trains (or
restores) the weather-augmented detector, optionally binds every drone
to a sampled FeFET chip instance, and flies the whole mission in one
device dispatch per die group.

Usage:
  PYTHONPATH=src python -m repro.launch.mission \
      --grid 14 --victims 10 --drones 4 --steps 70 --episodes 2
  PYTHONPATH=src python -m repro.launch.mission --policy deterministic
  PYTHONPATH=src python -m repro.launch.mission \
      --chip-instance 11 --chip-severity 2.5 [--uncalibrated]
  PYTHONPATH=src python -m repro.launch.mission --planner infogain \
      --flag-action skip --battery-uJ 250

``--policy``: bayes_adaptive (default) | bayes_fixed | deterministic —
the three systems benchmarks/mission_bench.py compares.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=14)
    ap.add_argument("--victims", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0,
                    help="world seed (episode e uses seed+e)")
    ap.add_argument("--corruption", default="snow",
                    choices=("fog", "frost", "motion", "snow"))
    ap.add_argument("--severity-hi", type=float, default=0.5,
                    help="worst-weather corner of the severity field")
    ap.add_argument("--drones", type=int, default=4)
    ap.add_argument("--battery-uJ", type=float, default=320.0,
                    help="per-sortie energy budget in microjoules")
    ap.add_argument("--steps", type=int, default=70)
    ap.add_argument("--episodes", type=int, default=1)
    ap.add_argument("--policy", default="bayes_adaptive",
                    choices=("bayes_adaptive", "bayes_fixed",
                             "deterministic"))
    ap.add_argument("--planner", default="lawnmower",
                    choices=("lawnmower", "infogain"))
    ap.add_argument("--flag-action", default="orbit",
                    choices=("orbit", "skip"))
    ap.add_argument("--conf-threshold", type=float, default=0.8)
    ap.add_argument("--mi-threshold", type=float, default=0.5)
    ap.add_argument("--r-min", type=int, default=4)
    ap.add_argument("--r-max", type=int, default=20)
    ap.add_argument("--chip-instance", type=int, default=None,
                    help="bind the fleet to a FeFET die sampled with "
                         "this seed (hw/ digital twin)")
    ap.add_argument("--chip-severity", type=float, default=1.0)
    ap.add_argument("--uncalibrated", action="store_true",
                    help="skip per-die head recalibration AND the "
                         "mission operating-point transfer")
    ap.add_argument("--age-rate", type=float, default=0.0,
                    help="simulated field-seconds of FeFET aging per "
                         "mission step (hw/aging.py); 0 disables the "
                         "lifetime loop")
    ap.add_argument("--age-epochs", type=int, default=4,
                    help="age/heal segments the mission is cut into")
    ap.add_argument("--auto-recalibrate", action="store_true",
                    help="heal drift advisories in flight: recalibrate "
                         "the aged die between segments and redeploy "
                         "(hw/redeploy.py)")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    default=True)
    ap.add_argument("--train-steps", type=int, default=None,
                    help="detector training steps (default: the shared "
                         "1600-step detector; CI smoke passes the "
                         "mission bench's 400 to reuse its cache)")
    ap.add_argument("--no-telemetry", dest="telemetry",
                    action="store_false", default=True,
                    help="disable per-die-group device-resident "
                         "telemetry + GRNG drift monitoring")
    ap.add_argument("--trace", type=str, default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace JSON of the "
                         "mission (per-drone tracks on the simulated "
                         "clock) to PATH")
    ap.add_argument("--metrics-out", type=str, default=None,
                    metavar="PREFIX",
                    help="write PREFIX.prom / PREFIX.json with the "
                         "mission summary + per-die telemetry")
    ap.add_argument("--profile", type=str, default=None, metavar="DIR",
                    help="capture a jax.profiler (XLA) trace of the "
                         "mission into DIR (TensorBoard-loadable)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro.mission import (MissionPolicy, UavConfig, WorldConfig,
                               fly_mission, trained_detector)
    from repro.obs.log import get_logger
    from repro.serving import TriagePolicy
    log = get_logger("mission")

    wcfg = WorldConfig(grid=args.grid, n_victims=args.victims,
                       seed=args.seed, corruption=args.corruption,
                       severity_hi=args.severity_hi)
    ucfg = UavConfig(n_drones=args.drones,
                     battery_J=args.battery_uJ * 1e-6)
    pol = MissionPolicy(
        mode=args.policy, planner=args.planner,
        flag_action=args.flag_action,
        triage=TriagePolicy(conf_threshold=args.conf_threshold,
                            mi_threshold=args.mi_threshold,
                            r_min=args.r_min, r_max=args.r_max))
    chips = None
    chip_note = ""
    if args.chip_instance is not None:
        from repro.hw import VariationSpec, sample_instances
        chips = sample_instances(
            args.chip_instance, 1,
            VariationSpec().scaled(args.chip_severity))[0]
        chip_note = (f" [chip seed={args.chip_instance} "
                     f"sev={args.chip_severity} "
                     f"{'UNCAL' if args.uncalibrated else 'cal'}]")

    det_kw = {} if args.train_steps is None else \
        {"steps": args.train_steps}
    from repro.obs.prof import trace_capture
    with trace_capture(args.profile):
        params, cfg = trained_detector(corruption=args.corruption,
                                       severity_hi=args.severity_hi,
                                       **det_kw)
        lifetime = None
        if args.age_rate > 0.0 or args.auto_recalibrate:
            from repro.hw.redeploy import LifetimeConfig
            lifetime = LifetimeConfig(
                age_rate=args.age_rate, epochs=args.age_epochs,
                auto_recalibrate=args.auto_recalibrate)
        res = fly_mission(wcfg, ucfg, pol, params=params, cfg=cfg,
                          chips=chips,
                          calibrated=not args.uncalibrated,
                          n_steps=args.steps, n_episodes=args.episodes,
                          fused=args.fused, telemetry=args.telemetry,
                          lifetime=lifetime)
    s = res.summary
    log.info(
        f"[{args.policy}/{args.planner}] "
        f"{s['episodes']}x{s['n_drones']} drones on "
        f"{s['grid']}x{s['grid']}{chip_note}: "
        f"rescued {s['rescued']}/{s['victims']}, "
        f"rescue delay {s['rescue_delay_s']:.0f}s, "
        f"coverage {100*s['coverage']:.0f}%, "
        f"false-verification rate "
        f"{100*s['false_verification_rate']:.1f}% "
        f"({s['false_verifications']}/{s['verifications']})")
    log.info(
        f"{s['decisions']} decisions, "
        f"{s['mean_samples_per_decision']:.1f} samples/decision, "
        f"{s['orbits']} orbits; energy "
        f"{1e6*s['energy_total_J']:.0f} uJ "
        f"(decisions {1e6*s['energy_decision_J']:.2f}, verify "
        f"{1e6*s['energy_verify_J']:.0f}, flight "
        f"{1e6*s['energy_flight_J']:.0f}); "
        f"host syncs {res.host_syncs}")
    for group, lt in (res.lifetime or {}).items():
        log.info("die lifetime", die_group=group,
                 age_s=lt["age_s"], advisories=lt["advisories"],
                 heals=lt["heals"], calib_epoch=lt["calib_epoch"])
    for group, t in (res.telemetry or {}).items():
        drift = t["drift"]
        if drift.get("advisory"):
            log.warning(drift["advisory"], die_group=group)
        else:
            log.info("die group healthy", die_group=group,
                     z_mean=round(drift["z_mean"], 2),
                     z_std=round(drift["z_std"], 2),
                     decisions=t["telemetry"]["decisions"])

    # Unified alert bus (obs/alerts): fold the per-die-group drift
    # statuses and lifetime heal events into one typed advisory stream
    # — post-hoc over the finished summary, so the mission hot path is
    # untouched.
    from repro.obs.alerts import AlertBus
    bus = AlertBus()
    for group, t in (res.telemetry or {}).items():
        bus.observe_drift(t.get("drift"), source=f"mission/{group}")
    for group, lt in (res.lifetime or {}).items():
        for ev in lt.get("events", []):
            bus.observe_heal(ev, source=f"mission/{group}")
    alerts = bus.to_json() if bus.advisories else None

    if args.trace:
        import json
        import os
        from repro.obs.trace import mission_trace
        d = os.path.dirname(args.trace)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(args.trace, "w") as f:
            json.dump(mission_trace(res.logs), f)
        log.info("trace written", path=args.trace)
    if args.metrics_out:
        from repro.obs.registry import mission_registry
        reg = mission_registry(s, telemetry=res.telemetry, alerts=alerts,
                               policy=args.policy, planner=args.planner)
        prom, js = reg.write(args.metrics_out)
        log.info("metrics written", prom=prom, json=js)


if __name__ == "__main__":
    main()
