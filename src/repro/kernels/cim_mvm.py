"""Pallas TPU kernel: deterministic CIM MVM (µ-only subarray, paper §V-B1).

The paper maps all deterministic layers onto µ-only subarrays via
im2col.  The numeric path is: 8-bit weights/inputs, analog 64-product
column sums, 6-bit SAR ADC per chunk, digital accumulation.  This
kernel reproduces that inside a 128-aligned blocked matmul: each k-block
contains bk/64 ADC chunks that are digitized *before* joining the
VMEM accumulator.

Inputs are pre-(fake)quantized dequant values; the ADC full-scale is a
runtime scalar (calibrated from activation/weight RMS on the host).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QuantConfig


def _cim_kernel(x_ref, w_ref, fs_ref, gain_ref, off_ref, o_ref, acc_ref, *,
                qcfg: QuantConfig, bk: int, n_real_chunks: int):
    """Chunked-ADC MVM with per-column ADC front-end nonideality.

    The bitline/SAR front-end of physical column n distorts the analog
    partial sum *before* conversion:  v = gain[n]·psum + offset[n]·lsb
    (gain error from capacitor-DAC mismatch, offset in LSB units from
    comparator offset — the repro/hw chip-instance model).  The digital
    side interprets codes ideally, so gain=1/offset=0 is bit-identical
    to the ideal path.

    K-padding chunks beyond ``n_real_chunks`` are masked out entirely:
    a pad chunk has no physical conversion, so it must not pick up the
    comparator offset (with offset=0 its code is 0 anyway).
    """
    kstep = pl.program_id(2)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    fs = fs_ref[0, 0]
    gain = gain_ref[...]                     # [1, bn]
    off = off_ref[...]                       # [1, bn]
    levels = 2 ** (qcfg.adc_bits - 1) - 1
    lsb = fs / levels
    kchunks = bk // qcfg.chunk

    for ci, c0 in enumerate(range(0, bk, qcfg.chunk)):   # chunks, unrolled
        psum = jnp.dot(x[:, c0:c0 + qcfg.chunk], w[c0:c0 + qcfg.chunk],
                       preferred_element_type=jnp.float32)
        v = gain * psum + off * lsb
        code = jnp.clip(jnp.round(v / lsb), -levels - 1, levels)
        real = kstep * kchunks + ci < n_real_chunks
        acc_ref[...] += jnp.where(real, code * lsb, 0.0)

    @pl.when(kstep == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("qcfg", "bb", "bk", "bn",
                                             "interpret"))
def cim_mvm_pallas(x, w, fs, qcfg: QuantConfig,
                   col_gain=None, col_offset=None,
                   bb: int = 128, bk: int = 128, bn: int = 128, *,
                   interpret: bool):
    """Chunked-ADC MVM. x:[B,K], w:[K,N], fs:[1,1] -> [B,N] float32.

    K must be a multiple of qcfg.chunk (the physical tile depth); B and N
    are zero-padded to block multiples.  Zero pads are ADC-safe: a zero
    partial sum quantizes to code 0 (gain scales zero to zero; the pad
    columns' gain/offset pads are 1/0).

    col_gain/col_offset: optional [N] per-column ADC gain and offset
    (offset in LSB units) — the nonideal chip-instance path.  Omitted =
    ideal ADC (bit-identical to the previous behaviour).
    ``interpret`` is a concrete bool, resolved by the caller
    (kernels/ops.py) so that it is part of the jit cache key.
    """
    b, kdim = x.shape
    n = w.shape[1]
    assert kdim % qcfg.chunk == 0, "K must be chunk-aligned (tile depth)"
    assert bk % qcfg.chunk == 0
    pb, pk, pn = (-b) % bb, (-kdim) % bk, (-n) % bn
    xp = jnp.pad(x, ((0, pb), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn)))
    if col_gain is None:
        col_gain = jnp.ones((n,), jnp.float32)
    if col_offset is None:
        col_offset = jnp.zeros((n,), jnp.float32)
    gp = jnp.pad(col_gain.astype(jnp.float32).reshape(1, n),
                 ((0, 0), (0, pn)), constant_values=1.0)
    op = jnp.pad(col_offset.astype(jnp.float32).reshape(1, n),
                 ((0, 0), (0, pn)))
    bp, kp = xp.shape
    np_ = wp.shape[1]
    out = pl.pallas_call(
        functools.partial(_cim_kernel, qcfg=qcfg, bk=bk,
                          n_real_chunks=kdim // qcfg.chunk),
        grid=(bp // bb, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bb, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bb, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bn), jnp.float32)],
        interpret=interpret,
    )(xp, wp, fs, gp, op)
    return out[:b, :n]
