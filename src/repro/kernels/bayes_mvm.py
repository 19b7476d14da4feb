"""Pallas TPU kernel: fused Bayesian head MVM (the paper's §IV dataflow).

Computes R logit samples  Y_r = X·(µ' + σ⊙ε_r)  for the weight-
decomposition head without ever materializing ε or the sampled weights
in HBM — the TPU analogue of the paper's in-memory σε subarray, where
randomness is generated at the point of compute.

Two variants, selected by the paper's shared-selection structure:

  * ``rank16`` (beyond-paper fast path): per (k-block) we accumulate the
    16 basis matmuls  basis_j += X·(σ⊙I_j)  in VMEM scratch and mix
    them with the [R,16] selection table at the last k step.  Cost is
    independent of R (≈18 MVM-equivalents); the sample distribution is
    *identical* to the faithful path because selection is shared
    layer-wide.  On a degraded chip instance (``cfg.read_sigma > 0``)
    the per-read noise term is full-rank per sample and cannot ride the
    basis; the kernel instead accumulates (x²)·(σ²) alongside and adds
    the exact logit-level projection N(0, read_sigma²·Σ x²σ²) at the
    final k step, hashed from the absolute sample index with the SAME
    stream as core/sampling.mix_samples — kernel-path serving matches
    the engine fast path draw-for-draw, and the faithful ``paper`` path
    in distribution (tests/test_hw_conformance.py).

  * ``paper`` (faithful path, optional 6-bit ADC): ε_r is materialized
    per sample in VMEM and each sample performs its own σε matmul, with
    partial sums optionally digitized every 64 rows (qcfg.chunk) at
    6-bit — the hardware's exact numeric order of operations.

VMEM per grid step (bB=bK=bN=128, R=20, f32):
  rank16: x 64K + µ/σ 128K + basis 16·64K=1M + acc 2·64K + out
          20·64K=1.25M  ≈ 2.6 MB; read_sigma > 0 adds the 64K (x²)(σ²)
          scratch plus an [R, bB, bN] noise-stack temporary in the
          final k step (R·64K ≈ 1.25 MB at R=20 — budget ≈ 3.9 MB on
          degraded instances)
  paper : x 64K + µ/σ 128K + eps 64K + out 1.25M                            ≈ 1.6 MB
Both well inside the ~16 MB v5e VMEM; matmul dims are 128-aligned (MXU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.clt_grng import GRNGConfig
from repro.core.quant import QuantConfig
from repro.kernels.clt_grng_kernel import (_device_current, _gauss_of, _hash3,
                                           _read_noise)


# ----------------------------------------------------------------------
# rank16 variant
# ----------------------------------------------------------------------
def _rank16_kernel(x_ref, mu_ref, sig_ref, sel_ref, out_ref,
                   basis_ref, accmu_ref, accxs_ref, *scratch,
                   cfg: GRNGConfig, bb: int, bk: int, bn: int,
                   row0: int, col0: int, sample0: int):
    # The (x²)·(σ²) accumulator exists only on degraded instances — the
    # ideal path (read_sigma == 0) allocates no noise scratch.
    accxq_ref = scratch[0] if cfg.read_sigma else None
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        basis_ref[...] = jnp.zeros_like(basis_ref)
        accmu_ref[...] = jnp.zeros_like(accmu_ref)
        accxs_ref[...] = jnp.zeros_like(accxs_ref)
        if cfg.read_sigma:
            accxq_ref[...] = jnp.zeros_like(accxq_ref)

    i = pl.program_id(0)
    j = pl.program_id(1)
    rows = (jnp.uint32(row0) + kstep * bk
            + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0))
    cols = (jnp.uint32(col0) + j * bn
            + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1))

    x = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    sig = sig_ref[...].astype(jnp.float32)

    accmu_ref[...] += jnp.dot(x, mu, preferred_element_type=jnp.float32)
    accxs_ref[...] += jnp.dot(x, sig, preferred_element_type=jnp.float32)
    if cfg.read_sigma:                       # (x²)·(σ²): noise projection
        accxq_ref[...] += jnp.dot(x * x, sig * sig,
                                  preferred_element_type=jnp.float32)
    for d in range(cfg.n_devices):           # 16 basis MVMs, unrolled
        i_d = _device_current(rows, cols, d, cfg)
        basis_ref[d, :, :] += jnp.dot(x, sig * i_d,
                                      preferred_element_type=jnp.float32)

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _finish():
        sel = sel_ref[...]                   # [R, 16]
        basis = basis_ref[...]               # [16, bB, bN]
        mixed = jax.lax.dot_general(
            sel, basis.reshape(cfg.n_devices, -1),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(sel.shape[0], *basis.shape[1:])        # [R, bB, bN]
        num = mixed - cfg.sum_mean * accxs_ref[...][None]
        if cfg.read_sigma:                   # degraded-instance twin
            # Per-read noise is full-rank per sample, so it cannot ride
            # the 16 basis MVMs; add its exact logit-level projection
            # N(0, read_sigma²·Σ_k x_k²σ_kn²) instead, drawn from the
            # SAME hash stream as core.sampling.mix_samples
            # (hash3(sample_idx, batch, col)) so kernel-path serving and
            # the engine fast path produce the same noise realization.
            bat = (i * bb
                   + jax.lax.broadcasted_iota(jnp.uint32, (bb, bn), 0))
            ncol = (jnp.uint32(col0) + j * bn
                    + jax.lax.broadcasted_iota(jnp.uint32, (bb, bn), 1))
            sigma_read = cfg.read_sigma * jnp.sqrt(
                jnp.maximum(accxq_ref[...], 0.0))        # [bB, bN]
            noise = jnp.stack([
                _gauss_of(_hash3(jnp.uint32(sample0 + r), bat, ncol,
                                 cfg.noise_seed))
                for r in range(sel.shape[0])])           # [R, bB, bN]
            num = num + noise * sigma_read[None]
        out_ref[...] = accmu_ref[...][None] + num * (1.0 / cfg.sum_std)


# ----------------------------------------------------------------------
# paper-faithful variant (optional chunked 6-bit ADC)
# ----------------------------------------------------------------------
def _paper_kernel(x_ref, mu_ref, sig_ref, sel_ref, fs_ref, out_ref, acc_ref, *,
                  cfg: GRNGConfig, qcfg: QuantConfig | None,
                  bk: int, bn: int, row0: int, col0: int, num_samples: int,
                  sample0: int):
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    j = pl.program_id(1)
    rows = (jnp.uint32(row0) + kstep * bk
            + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0))
    cols = (jnp.uint32(col0) + j * bn
            + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1))

    x = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    sig = sig_ref[...].astype(jnp.float32)
    sel = sel_ref[...]                       # [R, 16]

    currents = [_device_current(rows, cols, d, cfg) for d in range(cfg.n_devices)]

    def adc(psum, fs):
        if qcfg is None:
            return psum
        levels = 2 ** (qcfg.adc_bits - 1) - 1
        lsb = fs / levels
        return jnp.clip(jnp.round(psum / lsb), -levels - 1, levels) * lsb

    def chunked_mvm(w, fs):
        """X·w with ADC digitization every qcfg.chunk rows (hardware order)."""
        if qcfg is None:
            return jnp.dot(x, w, preferred_element_type=jnp.float32)
        acc = jnp.zeros((x.shape[0], w.shape[1]), jnp.float32)
        for c0 in range(0, bk, qcfg.chunk):
            psum = jnp.dot(x[:, c0:c0 + qcfg.chunk], w[c0:c0 + qcfg.chunk],
                           preferred_element_type=jnp.float32)
            acc = acc + adc(psum, fs)
        return acc

    fs_mu = fs_ref[0, 0]
    fs_se = fs_ref[0, 1]
    y_mu = chunked_mvm(mu, fs_mu)
    acc_ref[0, :, :] += y_mu
    for r in range(num_samples):             # per-sample σε MVM (faithful)
        raw = jnp.zeros((bk, bn), jnp.float32)
        for d in range(cfg.n_devices):
            raw = raw + sel[r, d] * currents[d]
        if cfg.read_sigma:                   # degraded-instance twin
            raw = raw + _read_noise(rows, cols, sample0 + r, cfg)
        eps_r = (raw - cfg.sum_mean) * (1.0 / cfg.sum_std)
        acc_ref[1 + r, :, :] += chunked_mvm(sig * eps_r, fs_se)

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _finish():
        out_ref[...] = acc_ref[0, :, :][None] + acc_ref[1:, :, :]


# ----------------------------------------------------------------------
# host-side wrappers (padding, grid setup)
# ----------------------------------------------------------------------
def _pad2(a, m0, m1):
    p0, p1 = (-a.shape[0]) % m0, (-a.shape[1]) % m1
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)))
    return a


@functools.partial(jax.jit, static_argnames=(
    "cfg", "qcfg", "mode", "row0", "col0", "sample0", "bb", "bk", "bn",
    "interpret"))
def bayes_mvm_pallas(x, mu, sigma, sel, fs, cfg: GRNGConfig,
                     qcfg: QuantConfig | None = None, mode: str = "rank16",
                     row0: int = 0, col0: int = 0, sample0: int = 0,
                     bb: int = 128, bk: int = 128, bn: int = 128, *,
                     interpret: bool):
    """Fused Bayesian head. x:[B,K], µ/σ:[K,N], sel:[R,16], fs:[1,2].

    Returns [R, B, N] float32 logit samples.  Zero-padding is safe: σ and
    µ pads are zero so padded rows/cols contribute nothing.
    ``interpret`` is a concrete bool, resolved by the caller
    (kernels/ops.py) so that it is part of the jit cache key.
    """
    b, kdim = x.shape
    _, n = mu.shape
    r = sel.shape[0]
    xp = _pad2(x, bb, bk)
    mup = _pad2(mu, bk, bn)
    sigp = _pad2(sigma, bk, bn)
    bp, kp = xp.shape
    np_ = mup.shape[1]
    grid = (bp // bb, np_ // bn, kp // bk)

    if mode == "rank16":
        out = pl.pallas_call(
            functools.partial(_rank16_kernel, cfg=cfg, bb=bb, bk=bk, bn=bn,
                              row0=row0, col0=col0, sample0=sample0),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((r, 16), lambda i, j, k: (0, 0)),
            ],
            out_specs=pl.BlockSpec((r, bb, bn), lambda i, j, k: (0, i, j)),
            out_shape=jax.ShapeDtypeStruct((r, bp, np_), jnp.float32),
            scratch_shapes=(
                [pltpu.VMEM((cfg.n_devices, bb, bn), jnp.float32),
                 pltpu.VMEM((bb, bn), jnp.float32),
                 pltpu.VMEM((bb, bn), jnp.float32)]
                # (x²)·(σ²) accumulator, degraded instances only
                + ([pltpu.VMEM((bb, bn), jnp.float32)]
                   if cfg.read_sigma else [])),
            interpret=interpret,
        )(xp, mup, sigp, sel)
    elif mode == "paper":
        out = pl.pallas_call(
            functools.partial(_paper_kernel, cfg=cfg, qcfg=qcfg, bk=bk, bn=bn,
                              row0=row0, col0=col0, num_samples=r,
                              sample0=sample0),
            grid=grid,
            in_specs=[
                pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
                pl.BlockSpec((r, 16), lambda i, j, k: (0, 0)),
                pl.BlockSpec((1, 2), lambda i, j, k: (0, 0)),
            ],
            out_specs=pl.BlockSpec((r, bb, bn), lambda i, j, k: (0, i, j)),
            out_shape=jax.ShapeDtypeStruct((r, bp, np_), jnp.float32),
            scratch_shapes=[pltpu.VMEM((1 + r, bb, bn), jnp.float32)],
            interpret=interpret,
        )(xp, mup, sigp, sel, fs)
    else:
        raise ValueError(mode)
    return out[:, :b, :n]
