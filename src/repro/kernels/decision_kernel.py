"""Pallas TPU kernel: fused sample→statistics decision update.

The serving hot path used to be a two-step dataflow:

    mix_samples:   [B,N,16] basis × [R,B,16] selections → [R,B,N] in HBM
    update_stats:  softmax + entropy over [R,B,N]        → O(B·N) sums

i.e. every triage decision materialized the full logit-sample tensor
only to immediately collapse it into five running sums.  The paper's
whole pitch is that a Bayesian sample costs 640 aJ on the FeFET engine;
paying an HBM round-trip of R·B·N floats per decision on the software
twin betrays that economy (Bayes2IMC and FeBiM flag exactly this
per-sample data movement as the barrier to in-memory BNN deployment).

This kernel fuses the whole decision update.  It consumes the rank-16
activation basis (``y_mu``, ``x_sigma``, ``m``, and ``x_sigsq`` on
degraded chip instances) plus the per-slot selection table and the
active-slot mask, and emits ONLY the sufficient-statistic deltas

    {sum_p [B,N], sum_psq [B,N], sum_ent [B], sum_entsq [B]}

(``n`` is the trivial count; the wrapper adds it).  Mixing, read-noise
projection, softmax, entropy, and the masked stats update all happen in
VMEM on [R, bB, bN] blocks; the peak HBM footprint of a decision no
longer carries an R·B·N term.

The softmax is a flash-attention-style ONLINE logsumexp over N: the
grid runs two phases per batch block — phase 0 streams the N blocks
once accumulating the running (max, sumexp) per (sample, row); phase 1
streams them again, normalizes each block against the finished
logsumexp, and accumulates the statistics.  Vocab-scale heads therefore
never hold [R, B, V] anywhere, in HBM *or* VMEM.

Read-noise twin: on a degraded instance (``cfg.read_sigma > 0``) each
logit sample carries the projected cycle-to-cycle read noise
N(0, read_sigma²·x_sigsq), hashed from the ABSOLUTE selection-stream
index with the same ``hash3`` stream as ``core.sampling.mix_samples``
and the rank16 ``bayes_mvm`` kernel — fused-path serving matches the
jnp fast path draw-for-draw, and escalation at later offsets extends
the stream exactly.

Oracle: ``kernels/ref.decision_stats_ref`` (pure jnp, no blocking),
asserted against ``update_stats(mix_samples(...))`` and against this
kernel in tests/test_decision_kernel.py.

VMEM per grid step (bb=8, bn=128, R=20, f32):
  m block 8·128·16·4 = 64K, mixed [R, bb, bn] 20·8·128·4 = 80K,
  row scratch 3·(R·bb)·4 ≈ 2K, out blocks 2·4K  →  well under 1 MB.
At vocab scale (bn=128 of N=151k) the footprint is unchanged — the
N dimension is streamed, never resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.clt_grng import GRNGConfig
from repro.kernels.clt_grng_kernel import _gauss_of, _hash3

_NEG = -1.0e30            # masked-logit fill: exp underflows to exactly 0


def _mix_logits(m_blk, sel, y_mu, x_sigma, x_sigsq, sidx, rows, *,
                cfg: GRNGConfig, i, k, bb, bn, n: int):
    """[R, bb, bn] logit samples for one (batch, column) block — the
    in-VMEM replica of core.sampling.mix_samples, padded cols → -1e30."""
    # per-slot mixing: [bb,R,16] × [bb,bn,16] → [bb,R,bn] (batched MXU).
    # HIGHEST: Mosaic's default contracts f32 in one bf16 pass, which
    # moves the per-sample probabilities by ~2e-3 on a TPU v5e.
    mix = jax.lax.dot_general(
        jnp.transpose(sel, (1, 0, 2)), m_blk,
        (((2,), (2,)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    mix = jnp.transpose(mix, (1, 0, 2))                  # [R, bb, bn]
    num = mix - cfg.sum_mean * x_sigma[None]
    if cfg.read_sigma:
        cols = (jnp.uint32(k * bn)
                + jax.lax.broadcasted_iota(jnp.uint32, (bb, bn), 1))
        # same stream as mix_samples: hash3(sample_idx, slot, column).
        # ``rows`` is the [bb, 1] block of GLOBAL slot ids — under the
        # shard_map lowering each shard hashes with its global rows, so
        # sharded draws match the single-device stream bit-for-bit.
        h = _hash3(sidx[:, :, None], rows[None], cols[None],
                   cfg.noise_seed)                       # [R, bb, bn]
        sigma_read = cfg.read_sigma * jnp.sqrt(
            jnp.maximum(x_sigsq, 0.0))                   # [bb, bn]
        num = num + _gauss_of(h) * sigma_read[None]
    logits = y_mu[None] + num * (1.0 / cfg.sum_std)
    valid = (k * bn + jax.lax.broadcasted_iota(
        jnp.int32, (bb, bn), 1)) < n
    return jnp.where(valid[None], logits, _NEG)


def _decision_kernel(*refs, cfg: GRNGConfig, bb: int, bn: int, n: int):
    """Grid (nb, 2, nn): phase 0 = online (max, sumexp) over the N
    stream; phase 1 = normalize + accumulate masked statistic deltas."""
    if cfg.read_sigma:
        (y_mu_ref, xs_ref, m_ref, sel_ref, mask_ref, xq_ref, sidx_ref,
         rows_ref,
         out_p_ref, out_psq_ref, out_ent_ref, out_entsq_ref,
         mrun_ref, lrun_ref, ent_ref) = refs
    else:
        (y_mu_ref, xs_ref, m_ref, sel_ref, mask_ref,
         out_p_ref, out_psq_ref, out_ent_ref, out_entsq_ref,
         mrun_ref, lrun_ref, ent_ref) = refs
        xq_ref = sidx_ref = rows_ref = None
    i = pl.program_id(0)
    phase = pl.program_id(1)
    k = pl.program_id(2)

    logits = _mix_logits(
        m_ref[...], sel_ref[...].astype(jnp.float32),
        y_mu_ref[...].astype(jnp.float32),
        xs_ref[...].astype(jnp.float32),
        xq_ref[...].astype(jnp.float32) if cfg.read_sigma else None,
        sidx_ref[...] if cfg.read_sigma else None,
        rows_ref[...] if cfg.read_sigma else None,
        cfg=cfg, i=i, k=k, bb=bb, bn=bn, n=n)            # [R, bb, bn]

    @pl.when((phase == 0) & (k == 0))
    def _init():
        mrun_ref[...] = jnp.full_like(mrun_ref, _NEG)
        lrun_ref[...] = jnp.zeros_like(lrun_ref)

    @pl.when(phase == 0)
    def _pass1():                            # online logsumexp update
        m_old = mrun_ref[...]                            # [R, bb]
        m_new = jnp.maximum(m_old, logits.max(-1))
        scale = jnp.exp(m_old - m_new)
        lrun_ref[...] = (lrun_ref[...] * scale
                         + jnp.exp(logits - m_new[..., None]).sum(-1))
        mrun_ref[...] = m_new

    @pl.when(phase == 1)
    def _pass2():                            # normalize + accumulate
        mask = mask_ref[...]                             # [bb, 1] f32
        lse = mrun_ref[...] + jnp.log(lrun_ref[...])     # [R, bb]
        logp = logits - lse[..., None]
        p = jnp.exp(logp)                    # padded cols: exactly 0
        out_p_ref[...] = p.sum(0) * mask
        out_psq_ref[...] = (p * p).sum(0) * mask

        @pl.when(k == 0)
        def _():
            ent_ref[...] = jnp.zeros_like(ent_ref)
        ent_ref[...] += -(p * logp).sum(-1)              # [R, bb]

        @pl.when(k == pl.num_programs(2) - 1)
        def _():
            ent = ent_ref[...]
            out_ent_ref[...] = ent.sum(0)[:, None] * mask
            out_entsq_ref[...] = (ent * ent).sum(0)[:, None] * mask


def _round_up(v: int, m: int) -> int:
    return v + (-v) % m


@functools.partial(jax.jit, static_argnames=(
    "cfg", "bb", "bn", "interpret"))
def decision_stats_pallas(y_mu, x_sigma, m, sel, cfg: GRNGConfig,
                          x_sigsq=None, sample_idx=None, mask=None,
                          rows=None, bb: int = 0, bn: int = 128, *,
                          interpret: bool) -> dict:
    """Fused decision-statistic deltas for one escalation round.

    y_mu/x_sigma: [B, N]; m: [B, N, 16] (``activation_basis``);
    sel: [R, B, 16] or [R, 16] selection vectors; x_sigsq: [B, N]
    (required when ``cfg.read_sigma > 0``); sample_idx: [R, B] or [R]
    absolute stream indices (the read-noise key — required on degraded
    instances, matching ``adaptive.stream_indices``); rows: [B] uint32
    GLOBAL slot ids for the read-noise hash (None = ``arange(B)``; a
    shard passes its global offsets so sharded draws match the
    single-device stream); mask: [B] bool — slots whose stats should
    advance (None = all).

    Returns the per-round deltas, already masked (inactive rows are 0):
    ``{sum_p [B,N] f32, sum_psq [B,N], sum_ent [B], sum_entsq [B]}`` —
    add them to running stats (``kernels.ops.decision_update`` does,
    together with the ``n`` count).  ``interpret`` is a concrete bool,
    resolved by the caller (kernels/ops.py) so that it is part of the
    jit cache key.
    """
    b, n = y_mu.shape
    if sel.ndim == 2:
        sel = jnp.broadcast_to(sel[:, None, :], (sel.shape[0], b, 16))
    r = sel.shape[0]
    if bb <= 0:
        bb = min(128, _round_up(b, 8))
    bp, np_ = _round_up(b, bb), _round_up(n, bn)
    grid = (bp // bb, 2, np_ // bn)

    def pad2(a):
        return jnp.pad(a.astype(jnp.float32),
                       ((0, bp - b), (0, np_ - n)))

    mask_col = (jnp.ones((b, 1), jnp.float32) if mask is None
                else jnp.asarray(mask).astype(jnp.float32).reshape(b, 1))
    operands = [
        pad2(y_mu), pad2(x_sigma),
        jnp.pad(m.astype(jnp.float32),
                ((0, bp - b), (0, np_ - n), (0, 0))),
        jnp.pad(sel.astype(jnp.float32), ((0, 0), (0, bp - b), (0, 0))),
        jnp.pad(mask_col, ((0, bp - b), (0, 0))),
    ]
    in_specs = [
        pl.BlockSpec((bb, bn), lambda i, p, k: (i, k)),          # y_mu
        pl.BlockSpec((bb, bn), lambda i, p, k: (i, k)),          # x_sigma
        pl.BlockSpec((bb, bn, 16), lambda i, p, k: (i, k, 0)),   # m
        pl.BlockSpec((r, bb, 16), lambda i, p, k: (0, i, 0)),    # sel
        pl.BlockSpec((bb, 1), lambda i, p, k: (i, 0)),           # mask
    ]
    if cfg.read_sigma:
        assert x_sigsq is not None, "degraded instance needs x_sigsq"
        assert sample_idx is not None, \
            "degraded instance needs absolute stream indices"
        sample_idx = jnp.asarray(sample_idx, jnp.uint32)
        if sample_idx.ndim == 1:
            sample_idx = jnp.broadcast_to(sample_idx[:, None], (r, b))
        if rows is None:
            rows = jnp.arange(b, dtype=jnp.uint32)
        rows = jnp.asarray(rows, jnp.uint32).reshape(b, 1)
        operands += [pad2(x_sigsq),
                     jnp.pad(sample_idx, ((0, 0), (0, bp - b))),
                     jnp.pad(rows, ((0, bp - b), (0, 0)))]
        in_specs += [
            pl.BlockSpec((bb, bn), lambda i, p, k: (i, k)),      # x_sigsq
            pl.BlockSpec((r, bb), lambda i, p, k: (0, i)),       # sample_idx
            pl.BlockSpec((bb, 1), lambda i, p, k: (i, 0)),       # rows
        ]

    out = pl.pallas_call(
        functools.partial(_decision_kernel, cfg=cfg, bb=bb, bn=bn, n=n),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, bn), lambda i, p, k: (i, k)),      # sum_p
            pl.BlockSpec((bb, bn), lambda i, p, k: (i, k)),      # sum_psq
            pl.BlockSpec((bb, 1), lambda i, p, k: (i, 0)),       # sum_ent
            pl.BlockSpec((bb, 1), lambda i, p, k: (i, 0)),       # sum_entsq
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, np_), jnp.float32),
            jax.ShapeDtypeStruct((bp, np_), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        scratch_shapes=(
            [pltpu.VMEM((r, bb), jnp.float32),       # running max
             pltpu.VMEM((r, bb), jnp.float32),       # running sumexp
             pltpu.VMEM((r, bb), jnp.float32)]),     # entropy accumulator
        interpret=interpret,
    )(*operands)
    sum_p, sum_psq, sum_ent, sum_entsq = out
    return {"sum_p": sum_p[:b, :n], "sum_psq": sum_psq[:b, :n],
            "sum_ent": sum_ent[:b, 0], "sum_entsq": sum_entsq[:b, 0]}


def decision_stats_sharded(y_mu, x_sigma, m, sel, cfg: GRNGConfig, *,
                           mesh, axis: str, x_sigsq=None, sample_idx=None,
                           mask=None, rows=None, bb: int = 0, bn: int = 128,
                           interpret: bool) -> dict:
    """Shard_map-native fused decision update over the slot (batch) axis.

    Each shard runs its own ``decision_stats_pallas`` grid on its local
    slots — every statistic in the output dict is slot-local, so the
    round's data path needs NO cross-device collectives.  Bit-identity
    with the single-device kernel comes from two global keys that shard
    trivially along B: ``sample_idx`` (absolute selection-stream index,
    already per-slot) and ``rows`` (global slot ids for the hash3
    read-noise stream; default ``arange(B)`` so shard k hashes with its
    true global offsets instead of local 0..B/k-1).

    ``interpret`` is the concrete bool the caller resolved
    (kernels/ops.py); every shard lowers with it.

    Requires ``B % mesh.shape[axis] == 0``; callers fall back to the
    unsharded kernel otherwise.
    """
    b, _ = y_mu.shape
    shards = mesh.shape[axis]
    if b % shards:
        raise ValueError(
            f"batch {b} not divisible by mesh axis {axis!r}={shards}")
    if sel.ndim == 2:
        sel = jnp.broadcast_to(sel[:, None, :], (sel.shape[0], b, 16))
    r = sel.shape[0]
    if mask is None:
        mask = jnp.ones((b,), jnp.bool_)
    P = jax.sharding.PartitionSpec

    if cfg.read_sigma:
        assert x_sigsq is not None, "degraded instance needs x_sigsq"
        assert sample_idx is not None, \
            "degraded instance needs absolute stream indices"
        sample_idx = jnp.asarray(sample_idx, jnp.uint32)
        if sample_idx.ndim == 1:
            sample_idx = jnp.broadcast_to(sample_idx[:, None], (r, b))
        if rows is None:
            rows = jnp.arange(b, dtype=jnp.uint32)
        rows = jnp.asarray(rows, jnp.uint32)

        def local(y_mu, x_sigma, m, sel, mask, x_sigsq, sample_idx, rows):
            return decision_stats_pallas(
                y_mu, x_sigma, m, sel, cfg, x_sigsq=x_sigsq,
                sample_idx=sample_idx, mask=mask, rows=rows,
                bb=bb, bn=bn, interpret=interpret)

        args = (y_mu, x_sigma, m, sel, mask, x_sigsq, sample_idx, rows)
        in_specs = (P(axis), P(axis), P(axis), P(None, axis), P(axis),
                    P(axis), P(None, axis), P(axis))
    else:

        def local(y_mu, x_sigma, m, sel, mask):
            return decision_stats_pallas(
                y_mu, x_sigma, m, sel, cfg, mask=mask,
                bb=bb, bn=bn, interpret=interpret)

        args = (y_mu, x_sigma, m, sel, mask)
        in_specs = (P(axis), P(axis), P(axis), P(None, axis), P(axis))

    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(axis), check_vma=False)
    return fn(*args)
