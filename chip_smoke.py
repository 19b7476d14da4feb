"""Bring-up smoke run of SAR triage serving on a TPU.

Usage, from the root of a checkout on a machine with a TPU:

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four-chip fleet check only

One chip: serves a 64-request synthetic SARD stream at the full width of
``SarCnnConfig()`` through ``repro.launch.serve.serve_sar`` on an ideal
die (phase A) and on a calibrated severity-2.5 die (phase B), then checks
one escalation round of the compiled decision kernel against the plain
jnp reference on both dies and compares fused with unfused verdicts
(phase C).  It asserts that the compiled round holds a Pallas TPU kernel
and that a second, identical pass of phase A compiles nothing.

Four chips: serves 256 requests through a 4-pool ``SarServingFleet``
whose gang round spans the chips, and through the same fleet with
``gang=False`` on one chip; verdicts and routes must be bitwise equal,
and every pool's arrays must sit on that pool's chip only.

Weights are random, made from a seed.  Everything runs in this one
process.  The lines before the last are smoke output, not benchmark
metrics.  The last line is one JSON object: ``{"ok": true, "device":
{...}}``.  With no TPU, or outside a checkout, it exits nonzero and
prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

N_REQUESTS = 64
N_SLOTS = 32
SEVERITY = 2.5
FLEET_POOLS = 4
FLEET_REQUESTS = 256
# Phase C: largest allowed |kernel - reference| of a round's per-sample
# means (probability, its square, entropy, its square).  Both sides are
# float32 with the reference's matmuls at "highest" precision; what is
# left is summation order, far below this.
REF_ATOL = 1e-4


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _smoke(**kw) -> None:
    print("smoke " + " ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


def _policy():
    from repro.serving.triage import TriagePolicy
    return TriagePolicy(conf_threshold=0.7, mi_threshold=0.05)


def _die(severity: float):
    if not severity:
        return None
    from repro.hw import VariationSpec, sample_instances
    return sample_instances(11, 1, VariationSpec().scaled(severity))[0]


def _check_served(out: dict, n_requests: int, policy) -> None:
    import numpy as np
    from repro.serving.triage import ESCALATE
    recs = out["verdicts"]
    assert out["decisions"] == n_requests == len(recs), out["decisions"]
    assert all(r["verdict"] != ESCALATE for r in recs)
    assert all(policy.r_min <= r["n_samples"] <= policy.r_max for r in recs)
    assert np.isfinite([[r["confidence"], r["mutual_information"]]
                        for r in recs]).all()


def serve_phase(name: str, params, cfg, chip, **kw) -> dict:
    """serve_sar over the 64-request stream; prints one smoke line."""
    from repro.launch.serve import serve_sar
    from repro.obs import prof
    from repro.serving.triage import VERDICT_NAMES
    policy = _policy()
    c0, s0 = prof.xla_compile_events(), prof.xla_compile_seconds()
    t0 = time.perf_counter()
    out = serve_sar(n_requests=N_REQUESTS, n_slots=N_SLOTS, adaptive=True,
                    policy=policy, params=params, cfg=cfg,
                    chip_instance=chip, calibrated=True, **kw)
    wall = time.perf_counter() - t0
    _check_served(out, N_REQUESTS, policy)
    mix = {}
    for r in out["verdicts"]:
        v = VERDICT_NAMES[r["verdict"]]
        mix[v] = mix.get(v, 0) + 1
    _smoke(phase=name, requests=N_REQUESTS, decisions=out["decisions"],
           mean_samples_per_decision=out["mean_samples_per_decision"],
           verdicts=",".join(f"{k}:{v}" for k, v in sorted(mix.items())),
           compiles=prof.xla_compile_events() - c0,
           compile_s=prof.xla_compile_seconds() - s0, wall_s=wall)
    return out


def _engine_with_admitted_round(params, cfg, chip):
    """An engine for the die with one full pool admitted (live shapes and
    real inputs of its first escalation round)."""
    import jax.numpy as jnp
    from repro.core.bayes_layer import sigma_of
    from repro.core.sampling import BayesHeadConfig
    from repro.hw import prepare_instance_head
    from repro.launch.serve import make_sar_stream
    from repro.serving import SarServingEngine
    policy = _policy()
    hcfg = BayesHeadConfig(num_samples=policy.r_max, mode="rank16",
                           grng=cfg.grng, compute_dtype=jnp.float32,
                           hoist_basis=True)
    head, hcfg = prepare_instance_head(
        params["head"]["mu"], sigma_of(params["head"]), hcfg, chip)
    eng = SarServingEngine(params, cfg, n_slots=N_SLOTS, policy=policy,
                           head=head, hcfg=hcfg, chip=chip)
    for r in make_sar_stream(N_SLOTS, image_size=cfg.image_size):
        eng.submit(r)
    eng.start()
    eng._admit()
    return eng


def _assert_round_has_kernel(eng) -> None:
    """The engine's round program, lowered at its live shapes, calls the
    compiled Pallas kernel (a ``tpu_custom_call``)."""
    import jax.numpy as jnp
    lowered = eng._round.lower(eng.pool, eng.stats, jnp.asarray(eng.base),
                               jnp.asarray(eng.active_mask()), eng._telem)
    assert "tpu_custom_call" in lowered.as_text(), "round has no TPU kernel"


def reference_phase(name: str, params, cfg, chip) -> None:
    """One round of the compiled decision kernel against the jnp
    reference, and the round program's Pallas kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decision_kernel import decision_stats_pallas
    from repro.kernels.ref import decision_stats_ref
    from repro.serving import adaptive
    eng = _engine_with_admitted_round(params, cfg, chip)
    grng = eng.hcfg.grng
    assert bool(grng.read_sigma) == bool(chip), grng.read_sigma
    _assert_round_has_kernel(eng)
    base = jnp.asarray(eng.base)
    active = jnp.asarray(eng.active_mask())
    n0 = jnp.zeros((N_SLOTS,), jnp.int32)
    sel = adaptive.stream_selections(grng, base, n0, eng.r_step)
    idx = adaptive.stream_indices(base, n0, eng.r_step)
    pool = eng.pool
    got = decision_stats_pallas(
        pool["y_mu"], pool["x_sigma"], pool["m"], sel, grng,
        x_sigsq=pool.get("x_sigsq"), sample_idx=idx, mask=active,
        interpret=False)
    with jax.default_matmul_precision("highest"):
        want = decision_stats_ref(
            pool["y_mu"], pool["x_sigma"], pool["m"], sel, grng,
            x_sigsq=pool.get("x_sigsq"), sample_idx=idx, mask=active)
    err = {k: float(np.max(np.abs(np.asarray(got[k]) - np.asarray(want[k])))
                    / eng.r_step) for k in want}
    assert all(np.isfinite(np.asarray(got[k])).all() for k in got)
    _smoke(phase=name, tpu_custom_call_in_round=True,
           max_abs_err_per_sample=max(err.values()), atol=REF_ATOL,
           **{f"err_{k}": v for k, v in err.items()})
    assert max(err.values()) <= REF_ATOL, err


def _phase_a(params, cfg) -> dict:
    """Phase A twice: the second, identical pass must compile nothing."""
    from repro.obs import prof
    serve_phase("A_warmup_ideal", params, cfg, None)
    warm = prof.xla_compile_events()
    assert warm > 0, "the compile counter saw no compile during warm-up"
    a = serve_phase("A_ideal", params, cfg, None)
    _smoke(phase="A_steady", compiles_after_warmup=warm,
           compiles_in_second_pass=prof.xla_compile_events() - warm)
    assert prof.xla_compile_events() == warm, (
        f"{prof.xla_compile_events() - warm} compiles in the second pass")
    return a


def _phase_b(params, cfg, chip) -> None:
    b = serve_phase(f"B_sev{SEVERITY}", params, cfg, chip)
    assert b["chip_read_sigma"] > 0.0


def _fused_vs_unfused(params, cfg, a: dict) -> None:
    u = serve_phase("C_unfused_ideal", params, cfg, None, fused=False)
    pairs = list(zip(a["verdicts"], u["verdicts"]))
    assert all(x["rid"] == y["rid"] for x, y in pairs)
    _smoke(phase="C_fused_vs_unfused", requests=N_REQUESTS,
           verdicts_agree=sum(x["verdict"] == y["verdict"] for x, y in pairs),
           samples_agree=sum(x["n_samples"] == y["n_samples"]
                             for x, y in pairs),
           max_confidence_diff=max(abs(x["confidence"] - y["confidence"])
                                   for x, y in pairs))


def _run(failed: list, name: str, fn, *args):
    """Run one phase; a failure is printed and recorded, and the later
    phases still run so one call on the chip shows every fault."""
    try:
        return fn(*args)
    except Exception:                                     # noqa: BLE001
        traceback.print_exc()
        failed.append(name)
        return None


def one_chip(params, cfg) -> list:
    """Phases A-C; returns the names of the phases that failed."""
    failed = []
    a = _run(failed, "A", _phase_a, params, cfg)
    chip = _die(SEVERITY)
    _run(failed, "B", _phase_b, params, cfg, chip)
    _run(failed, "C_ref_ideal", reference_phase, "C_ref_ideal", params, cfg,
         None)
    _run(failed, "C_ref_sev", reference_phase, f"C_ref_sev{SEVERITY}",
         params, cfg, chip)
    if a is not None:
        _run(failed, "C_unfused", _fused_vs_unfused, params, cfg, a)
    return failed


def _fleet(params, cfg, gang: bool):
    from repro.launch.serve import make_sar_stream
    from repro.serving import SarServingFleet
    fleet = SarServingFleet(params, cfg, n_pools=FLEET_POOLS,
                            slots_per_pool=N_SLOTS, policy=_policy(),
                            gang=gang)
    for r in make_sar_stream(FLEET_REQUESTS, corrupt_frac=0.25,
                             image_size=cfg.image_size):
        fleet.submit(r)
    t0 = time.perf_counter()
    out = fleet.run()
    recs = sorted(((r.rid, fleet.routes[r.rid], r.verdict, r.confidence,
                    r.mutual_information, r.n_samples)
                   for e in fleet.engines for r in e.metrics.records))
    _smoke(phase="fleet_gang" if gang else "fleet_one_chip",
           pools=FLEET_POOLS, gang=out["gang"], decisions=out["decisions"],
           routed=",".join(map(str, out["routed_per_pool"])),
           host_syncs=out["host_syncs"], wall_s=time.perf_counter() - t0)
    return fleet, out, recs


def four_chips(params, cfg) -> None:
    import jax
    gang, out_g, recs_g = _fleet(params, cfg, gang=True)
    _, out_s, recs_s = _fleet(params, cfg, gang=False)
    assert out_g["gang"] and not out_s["gang"]
    assert out_g["decisions"] == out_s["decisions"] == FLEET_REQUESTS
    assert recs_g == recs_s, "gang and one-chip verdicts or routes differ"
    mesh_devices = list(gang.mesh.devices.flat)
    for p, eng in enumerate(gang.engines):
        leaves = jax.tree.leaves((eng._params, eng.pool, eng.stats,
                                  eng._telem))
        placed = set().union(*(x.devices() for x in leaves))
        assert placed == {mesh_devices[p]}, (p, placed)
    _smoke(phase="fleet_compare", verdicts_and_routes_bitwise_equal=True,
           pool_arrays_on_own_chip=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving phases on one chip; 4: only the "
                         "four-pool fleet across four chips vs one chip")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}: run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU found: JAX platform is {devices[0].platform!r}")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} TPUs, found "
              f"{len(devices)}")
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.sar_cnn import SarCnnConfig, init_sar_cnn
    _smoke(phase="setup", platform=devices[0].platform,
           kind=repr(devices[0].device_kind), count=len(devices),
           compile_cache=enable_compile_cache())
    cfg = SarCnnConfig()
    params = init_sar_cnn(jax.random.PRNGKey(3), cfg)
    t0 = time.perf_counter()
    if args.chips == 4:
        failed = []
        _run(failed, "fleet", four_chips, params, cfg)
    else:
        failed = one_chip(params, cfg)
    _smoke(phase="done", wall_s=time.perf_counter() - t0)
    if failed:
        _fail(f"phases failed: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
