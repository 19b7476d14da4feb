"""Public jit'd entry points for the kernels package.

These wrappers own host-side concerns: selection-table generation,
ADC full-scale calibration, dtype plumbing, and the interpret mode.
``interpret=None`` resolves here, outside every kernel's ``jax.jit``
(compile on TPU, interpret elsewhere — kernels/backend.py), so the
jitted kernels only ever see a concrete bool.  They are the drop-in
counterparts of the pure-jnp paths in core/sampling.py and core/cim.py,
asserted allclose in tests/test_kernels.py.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import clt_grng as g
from repro.core.quant import QuantConfig, adc_full_scale
from repro.kernels.backend import resolve_interpret
from repro.kernels.bayes_mvm import bayes_mvm_pallas
from repro.kernels.cim_mvm import cim_mvm_pallas
from repro.kernels.clt_grng_kernel import grng_eps_pallas
from repro.kernels.decision_kernel import (decision_stats_pallas,
                                           decision_stats_sharded)


def grng_eps(cfg: g.GRNGConfig, n_rows: int, n_cols: int, num_samples: int,
             sample0: int = 0, row0: int = 0, col0: int = 0,
             interpret: bool | None = None) -> jnp.ndarray:
    """CLT-GRNG ε block via the Pallas kernel. -> [R, n_rows, n_cols]."""
    sel = g.selections(cfg, num_samples, sample0)
    bk = min(256, max(128, n_rows))
    bn = min(256, max(128, n_cols))
    return grng_eps_pallas(
        sel, cfg, n_rows, n_cols, row0=row0, col0=col0, sample0=sample0,
        bk=bk, bn=bn, interpret=resolve_interpret(interpret))


def bayes_head_mvm(x: jnp.ndarray, mu_prime: jnp.ndarray, sigma: jnp.ndarray,
                   cfg: g.GRNGConfig, num_samples: int, sample0: int = 0,
                   mode: str = "rank16", qcfg: QuantConfig | None = None,
                   row0: int = 0, col0: int = 0,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Fused Bayesian head: [R, B, N] logit samples.

    mode='rank16'  — R-independent fast path (exact distribution).  On a
                     degraded instance (``cfg.read_sigma > 0``) it adds
                     the logit-level read-noise projection, matching the
                     core/sampling.mix_samples hash stream draw-for-draw
                     (oracle: ref.bayes_mvm_rank16_ref).
    mode='paper'   — faithful per-sample path (per-cell read noise);
                     pass qcfg to enable the 6-bit chunked-ADC numeric
                     pipeline.
    """
    sel = g.selections(cfg, num_samples, sample0)
    if qcfg is not None and not qcfg.enabled:
        qcfg = None
    if qcfg is not None:
        assert mode == "paper", "ADC path requires hardware sample order"
        x_rms = jnp.sqrt(jnp.mean(x.astype(jnp.float32) ** 2) + 1e-12)
        mu_rms = jnp.sqrt(jnp.mean(mu_prime.astype(jnp.float32) ** 2) + 1e-12)
        # σε RMS: Var[σ·ε] ≈ E[σ²] for standardized ε.
        se_rms = jnp.sqrt(jnp.mean(sigma.astype(jnp.float32) ** 2) + 1e-12)
        fs = jnp.stack([adc_full_scale(x_rms, mu_rms, qcfg),
                        adc_full_scale(x_rms, se_rms, qcfg)]).reshape(1, 2)
    else:
        fs = jnp.zeros((1, 2), jnp.float32)
    return bayes_mvm_pallas(
        x, mu_prime, sigma, sel, fs, cfg, qcfg=qcfg, mode=mode,
        row0=row0, col0=col0, sample0=sample0,
        interpret=resolve_interpret(interpret))


def decision_update(stats: dict, abasis: dict, sel: jnp.ndarray,
                    cfg: g.GRNGConfig, sample_idx=None, mask=None,
                    interpret: bool | None = None, shard=None,
                    rows=None) -> dict:
    """Fused drop-in for ``update_stats(stats, mix_samples(...), mask)``.

    Folds one escalation round into the running sufficient statistics
    via the fused decision kernel (decision_kernel.py): mixing, the
    degraded-instance read-noise projection, online softmax over N,
    entropy, and the active-slot masking all run in VMEM — the [R,B,N]
    logit-sample tensor never exists.

    stats: ``adaptive.init_stats`` pytree; abasis:
    ``core.sampling.activation_basis`` output; sel: [R, B, 16] or
    [R, 16]; sample_idx: absolute stream indices ([R, B] or [R],
    ``adaptive.stream_indices``) — the read-noise key on degraded
    instances; mask: [B] bool, False rows keep their old sums.

    shard: optional ``(mesh, axis_name)`` — route the round through the
    shard_map-native kernel (``decision_stats_sharded``): each device
    runs its own Pallas grid on its slot shard, stats stay slot-local,
    and ``rows`` ([B] uint32 global slot ids, default ``arange(B)``)
    keys the read-noise hash so sharded draws are bit-identical to the
    single-device stream.

    Verdict-equivalent to the jnp path (tests/test_decision_kernel.py);
    numerics agree to fp32 tolerance (online vs one-shot logsumexp
    reduction order).
    """
    interpret = resolve_interpret(interpret)
    if shard is not None:
        mesh, axis = shard
        delta = decision_stats_sharded(
            abasis["y_mu"], abasis["x_sigma"], abasis["m"], sel, cfg,
            mesh=mesh, axis=axis, x_sigsq=abasis.get("x_sigsq"),
            sample_idx=sample_idx, mask=mask, rows=rows,
            interpret=interpret)
    else:
        delta = decision_stats_pallas(
            abasis["y_mu"], abasis["x_sigma"], abasis["m"], sel, cfg,
            x_sigsq=abasis.get("x_sigsq"), sample_idx=sample_idx, mask=mask,
            rows=rows, interpret=interpret)
    r = sel.shape[0]
    n_delta = jnp.full_like(stats["n"], r)
    if mask is not None:
        n_delta = jnp.where(jnp.asarray(mask), n_delta, 0)
    return {
        "n": stats["n"] + n_delta,
        "sum_p": stats["sum_p"] + delta["sum_p"],
        "sum_psq": stats["sum_psq"] + delta["sum_psq"],
        "sum_ent": stats["sum_ent"] + delta["sum_ent"],
        "sum_entsq": stats["sum_entsq"] + delta["sum_entsq"],
    }


def _measured_full_scale(x, w, qcfg: QuantConfig):
    """One-time ADC range calibration from measured partial-sum RMS
    (sampled rows for cost) — see core/cim.py for why the analytic
    independence model under-scales."""
    xs = x[: min(16, x.shape[0])].astype(jnp.float32)
    kc = x.shape[1] // qcfg.chunk
    xb = xs.reshape(xs.shape[0], kc, qcfg.chunk)
    wb = w.astype(jnp.float32).reshape(kc, qcfg.chunk, w.shape[1])
    ps = jnp.einsum("bkc,kcn->bkn", xb, wb)
    return qcfg.adc_clip_sigmas * jnp.sqrt(jnp.mean(ps ** 2) + 1e-12)


def cim_matmul(x: jnp.ndarray, w: jnp.ndarray, qcfg: QuantConfig,
               interpret: bool | None = None) -> jnp.ndarray:
    """Deterministic chunked-ADC CIM matmul (µ-only subarray)."""
    fs = _measured_full_scale(x, w, qcfg).reshape(1, 1)
    return cim_mvm_pallas(x, w, fs, qcfg,
                          interpret=resolve_interpret(interpret))


def cim_matmul_nonideal(x: jnp.ndarray, w: jnp.ndarray, qcfg: QuantConfig,
                        col_gain: jnp.ndarray, col_offset: jnp.ndarray,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Chip-instance CIM matmul: per-column ADC gain/offset (repro/hw).

    ``col_gain``/``col_offset`` [N] come from a sampled ChipInstance
    (hw/instance.py: ``adc_gain``/``adc_offset`` tiled over the output
    columns).  Conductance programming error is a *weight* perturbation —
    fold it into ``w`` with ``hw.instance.program_weights`` before the
    call.  Oracle: kernels/ref.cim_mvm_nonideal_ref.
    """
    fs = _measured_full_scale(x, w, qcfg).reshape(1, 1)
    return cim_mvm_pallas(x, w, fs, qcfg, col_gain=col_gain,
                          col_offset=col_offset,
                          interpret=resolve_interpret(interpret))
