"""Whether what the timed path served is correct.

After the window, a sample of the served requests (drawn from the seed,
with the request that escalated furthest in it) is recomputed by the
plain reference (``reference.py``) from its image and the trained
parameters, on the same selection-stream positions.  Per request the
reading is the largest of

* the gap between the served confidence and the reference's, and
  between the served mutual information and the reference's, both at
  the served sample count;
* where the served verdict trajectory (escalate until the served count,
  then the served verdict) or prediction differs from the reference's,
  the reference's distance from the decision boundary it would have had
  to cross.

The compared number is the largest reading, ``decision_gap``, against
the configuration's ``check.decision_gap``.  Stream
positions and slots are not read from the program: they follow from
the admission order (each admitted request reserves ``r_max`` stream
positions, FIFO) and a replay of the slot free list from the admission
and verdict time stamps of the served records.
"""

from __future__ import annotations

from collections import defaultdict
import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

DOTS = {"f32": ref.dot_f32, "default": ref.dot_default,
        "bf16": ref.dot_bf16}
MIX = {"highest": jax.lax.Precision.HIGHEST,
       "high": jax.lax.Precision.HIGH}


def _admissions(records):
    """[(admit_s, [rids in FIFO order])] of one engine, in time order."""
    groups = defaultdict(list)
    for r in records:
        groups[r.admit_s].append(r.rid)
    return [(t, sorted(rids)) for t, rids in sorted(groups.items())]


def stream_bases(records, r_max: int) -> dict:
    out, k = {}, 0
    for _, rids in _admissions(records):
        for rid in rids:
            out[rid] = k * r_max
            k += 1
    return out


def _evaluate(cfg: dict, dot: str, mix: str, r_step: int, args):
    """Reference trajectories of the requests with images ``images``."""
    d, pol = DOTS[dot], cfg["policy"]
    g = ref.Grng.from_config(cfg["model"]["grng"])

    def evaluate(params, images, base):
        feats = ref.trunk_ideal(params, images, d)
        ab = ref.activation_basis(ref.deploy_head(params["head"], g),
                                  feats, d)
        return ref.trajectories(ab, g, base, pol["r_max"], r_step, pol,
                                MIX[mix])

    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(evaluate)(*args))


def served_outcome(traj: dict, r_step: int) -> dict:
    """The trajectory a served request would show if the reference's
    own verdicts were served: its stopping round and outcome."""
    v = np.asarray(traj["verdict"])
    stop = np.argmax(v != ref.ESCALATE, axis=1)
    take = lambda a: np.take_along_axis(np.asarray(a), stop[:, None], 1)[:, 0]
    return {"n_samples": (stop + 1) * r_step, "verdict": take(v),
            "confidence": take(traj["confidence"]),
            "mutual_information": take(traj["mutual_information"]),
            "prediction": take(traj["prediction"])}


def readings(served: dict, traj: dict, r_step: int, r_max: int):
    """Per-request gap of the served outcome against the reference."""
    n = np.asarray(served["n_samples"])
    rounds = np.asarray(traj["verdict"]).shape[1]
    ok_n = (n % r_step == 0) & (n >= r_step) & (n <= r_max)
    r = np.clip(n // r_step - 1, 0, rounds - 1)
    at = lambda a: np.take_along_axis(np.asarray(a), r[:, None], 1)[:, 0]
    gap = np.maximum(
        np.abs(np.asarray(served["confidence"]) - at(traj["confidence"])),
        np.abs(np.asarray(served["mutual_information"])
               - at(traj["mutual_information"])))
    v_ref = np.asarray(traj["verdict"])
    margin = np.asarray(traj["margin"])
    early = (np.arange(rounds)[None] < r[:, None]) & (v_ref != ref.ESCALATE)
    gap = np.maximum(gap, np.where(early, margin, 0.0).max(axis=1))
    wrong = at(v_ref) != np.asarray(served["verdict"])
    gap = np.where(wrong, np.maximum(gap, at(margin)), gap)
    wrong_pred = at(traj["prediction"]) != np.asarray(served["prediction"])
    gap = np.where(wrong_pred, np.maximum(gap, at(traj["pred_margin"])), gap)
    gap = np.where(ok_n & np.isfinite(gap), gap, np.inf)
    return gap


def compare(cfg: dict, params, system, image_of, due: list, seed: int, *,
            submitted: int, control: str | None = None, log=None) -> list:
    """[(name, value, limit, kind)] — kind "max": value <= limit passes,
    "min": value >= limit passes.  ``due``: rids whose answers are due
    in the window (the sample is drawn from them); ``submitted``: rids
    0..submitted-1 were all sent during the run and must be answered.
    ``control``: "reference_bf16" puts the reference, computed one
    precision step below the configuration's, in the program's place."""
    lim = cfg["check"]
    r_max = cfg["policy"]["r_max"]
    r_step = system.r_step
    recs, base_of = {}, {}
    for eng in system.engines:
        base_of.update(stream_bases(eng.metrics.records, r_max))
        recs.update((rec.rid, rec) for rec in eng.metrics.records)
    missing = sum(1 for rid in range(submitted) if rid not in recs)
    due = [rid for rid in due if rid in recs]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4EC])
    n_check = min(int(lim["sample"]), len(due))
    longest = max(due, key=lambda rid: (recs[rid].n_samples, -rid),
                  default=None)
    pick = set(rng.choice(len(due), n_check, replace=False).tolist())
    chosen = sorted({due[i] for i in pick} | (
        {longest} if longest is not None else set()))
    images = np.stack([image_of(r) for r in chosen])
    base = np.asarray([base_of[r] for r in chosen], np.uint32)
    args = (params, jnp.asarray(images), jnp.asarray(base))
    traj = _evaluate(cfg, cfg["precision"]["reference_dot"], "highest",
                     r_step, args)
    if control == "reference_bf16":
        served = served_outcome(_evaluate(cfg, "bf16", "high", r_step, args),
                                r_step)
    else:
        served = {k: np.asarray([getattr(recs[r], k) for r in chosen])
                  for k in ("n_samples", "verdict", "confidence",
                            "mutual_information", "prediction")}
    gap = readings(served, traj, r_step, r_max)
    if log is not None and len(gap):
        q = np.quantile(gap, [0.5, 0.9, 0.99])
        log(f"gaps n={len(gap)} p50={q[0]:.3g} p90={q[1]:.3g} "
            f"p99={q[2]:.3g} max={gap.max():.3g} "
            f"over_1e-4={int((gap > 1e-4).sum())}")
    widest = float(gap.max()) if len(gap) else float("inf")
    return [("decision_gap", widest, float(lim["decision_gap"]), "max"),
            ("checked", len(chosen), int(lim["min_checked"]), "min"),
            ("missing", missing, 0, "max")]

