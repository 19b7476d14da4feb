"""Pallas TPU kernel: block CLT-GRNG ε generation.

Generates the standardized subset-sum samples ε[r, k, n] for a weight
block entirely on-chip: virtual device currents are re-derived from the
integer hash of the (row, col, device) coordinate (write-free — zero
HBM traffic for randomness), masked by the shared selection vectors and
summed.  The only HBM input is the [R, 16] selection table (64·R bytes);
the output block never round-trips intermediate state.

VMEM budget per grid step (defaults bK=bN=256, R≤32):
  out block  R·256·256·4  ≤ 8 MB @ R=32  (use bK=bN=128 for large R)
  hash temporaries 256·256·4 ≈ 0.25 MB ×3
Matmul-free: the j-loop is 16 unrolled fused multiply-adds on the VPU.
MXU alignment: block dims are multiples of 128 on the minor axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.clt_grng import GRNGConfig

_C1 = 0x9E3779B9
_C2 = 0x85EBCA6B
_C3 = 0xC2B2AE35


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x


def _hash3(k, n, j, seed: int):
    # Explicit uint32 coercion: program_id-derived indices arrive as
    # int32, and int32 hash arithmetic diverges (arithmetic >> shifts).
    k = jnp.asarray(k).astype(jnp.uint32)
    n = jnp.asarray(n).astype(jnp.uint32)
    j = jnp.asarray(j).astype(jnp.uint32)
    h = _mix32(j * jnp.uint32(_C3) + jnp.uint32(seed))
    h = _mix32(n * jnp.uint32(_C2) + h)
    h = _mix32(k * jnp.uint32(_C1) + h)
    return h


def _small_u32_to_f32(v):
    """Exact float of a uint32 below 2**31, cast through int32: Mosaic
    has no direct uint32 -> float32 conversion."""
    return v.astype(jnp.int32).astype(jnp.float32)


def _gauss_of(h):
    """CLT-of-bytes normal surrogate (core.hashing.gaussianish, inlined)."""
    b0 = _small_u32_to_f32(h & jnp.uint32(0xFF))
    b1 = _small_u32_to_f32((h >> jnp.uint32(8)) & jnp.uint32(0xFF))
    b2 = _small_u32_to_f32((h >> jnp.uint32(16)) & jnp.uint32(0xFF))
    return (b0 + b1 + b2 - 382.5) * (1.0 / 127.99316)


def _device_current(rows, cols, j: int, cfg: GRNGConfig):
    """Virtual device current I(k, n, j) for a coordinate block."""
    h = _hash3(rows, cols, j, cfg.seed)
    bit = _small_u32_to_f32((h >> jnp.uint32(31)) & jnp.uint32(1))
    out = cfg.i_lo + cfg.delta_i * bit + cfg.gamma * _gauss_of(h)
    if cfg.imprint:                          # aged-die twin (hw/aging)
        out = out + cfg.imprint * _gauss_of(
            _hash3(rows, cols, j, cfg.imprint_seed))
    return out


def _read_noise(rows, cols, r_abs: int, cfg: GRNGConfig):
    """Cycle-to-cycle read noise at absolute sample index ``r_abs`` —
    bit-identical to core.clt_grng.read_noise_at."""
    h = _hash3(rows, cols, r_abs, cfg.noise_seed)
    return cfg.read_sigma * _gauss_of(h)


def _grng_kernel(sel_ref, out_ref, *, cfg: GRNGConfig, bk: int, bn: int,
                 row0: int, col0: int, sample0: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    rows = (jnp.uint32(row0) + i * bk
            + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0))
    cols = (jnp.uint32(col0) + j * bn
            + jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1))
    sel = sel_ref[...]                       # [R, 16]
    r = sel.shape[0]
    raw = jnp.zeros((r, bk, bn), jnp.float32)
    for d in range(cfg.n_devices):           # 16, unrolled
        i_d = _device_current(rows, cols, d, cfg)          # [bk, bn]
        raw = raw + sel[:, d][:, None, None] * i_d[None]
    if cfg.read_sigma:                       # degraded-instance twin
        raw = raw + jnp.stack([_read_noise(rows, cols, sample0 + ri, cfg)
                               for ri in range(r)])
    out_ref[...] = (raw - cfg.sum_mean) * (1.0 / cfg.sum_std)


@functools.partial(jax.jit, static_argnames=(
    "cfg", "n_rows", "n_cols", "row0", "col0", "sample0", "bk", "bn",
    "interpret"))
def grng_eps_pallas(sel: jnp.ndarray, cfg: GRNGConfig, n_rows: int,
                    n_cols: int, row0: int = 0, col0: int = 0,
                    sample0: int = 0, bk: int = 256, bn: int = 256, *,
                    interpret: bool) -> jnp.ndarray:
    """ε block via Pallas. sel: [R, 16] float32 -> [R, n_rows, n_cols].

    ``sample0``: absolute index of sel[0] in the selection stream — only
    read (for the noise hash) when ``cfg.read_sigma > 0``.
    ``interpret`` is a concrete bool, resolved by the caller
    (kernels/ops.py) so that it is part of the jit cache key.
    """
    r = sel.shape[0]
    pad_k = (-n_rows) % bk
    pad_n = (-n_cols) % bn
    kp, np_ = n_rows + pad_k, n_cols + pad_n
    out = pl.pallas_call(
        functools.partial(_grng_kernel, cfg=cfg, bk=bk, bn=bn,
                          row0=row0, col0=col0, sample0=sample0),
        grid=(kp // bk, np_ // bn),
        in_specs=[pl.BlockSpec((r, 16), lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((r, bk, bn), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((r, kp, np_), jnp.float32),
        interpret=interpret,
    )(sel)
    return out[:, :n_rows, :n_cols]
