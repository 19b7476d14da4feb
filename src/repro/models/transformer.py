"""Decoder-only / encoder-decoder / cross-attention transformer LM.

One flexible implementation drives 8 of the 10 assigned architectures
(dense, MoE, SWA, qk-norm, QKV-bias, whisper enc-dec, llama-vision
cross-attn); mamba2/zamba2 live in mamba2.py / hybrid.py.

Structure: pre-norm blocks, `lax.scan` over stacked layer params
(leading L dim on every leaf) with configurable remat.  Enc-dec models
(whisper) carry an ``xattn`` sub-block inside every decoder layer
(self-attn → cross-attn → MLP, whisper order); VLM models (llama-3.2-
vision) interleave dedicated cross-attention layers (with their own MLP,
llama-3.2 style) every ``cross_attn_every`` self layers.

The LM head is the paper's Bayesian weight-decomposition layer (µ, ρ) —
trained with Bayes-by-backprop, served with CLT-GRNG sampling.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import bayes_layer
from repro.core.bayes_layer import BayesDenseConfig
from repro.core.clt_grng import GRNGConfig
from repro.core.lfsr import indexed_selections
from repro.models import attention as attn
from repro.models import blocks
from repro.models.moe import init_moe, moe_apply


# ----------------------------------------------------------------------
# Config
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"        # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 1024
    vocab: int = 1024
    vocab_pad_multiple: int = 256
    norm: str = "rms"            # rms | ln
    mlp: str = "swiglu"          # swiglu | gelu
    use_rope: bool = True
    rope_theta: float = 1e6
    qk_norm: bool = False
    qkv_bias: bool = False
    swa_window: int | None = None
    learned_pos: int = 0         # >0: learned positional table size (whisper)
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # SSM (mamba2 / zamba2)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_ngroups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    hybrid_attn_every: int = 0   # zamba2: shared attn block every N ssm layers
    # enc-dec (whisper: encoder frames are a stubbed modality frontend)
    encoder_layers: int = 0
    n_frames: int = 0
    # vlm (llama-3.2-vision: patch embeds stubbed)
    cross_attn_every: int = 0
    n_image_tokens: int = 0
    # Paper technique: Bayesian LM head
    bayesian_head: bool = True
    uq_samples: int = 8
    head_mode: str = "rank16"    # paper | rank16 | moment
    sigma_init: float = 0.03
    prior_sigma: float = 0.1
    kl_weight: float = 1e-5
    # compute
    dtype: Any = jnp.bfloat16
    remat: str = "full"          # full | dots | none
    attn_chunk_q: int = 512
    attn_chunk_kv: int = 1024
    # mesh hints (set by the launcher; () disables constraints)
    batch_axes: tuple = ()
    model_axis_size: int = 0
    # §Perf I2b: explicit Megatron TP linears (shard_map row/col parallel
    # with bf16 psum) instead of GSPMD-inferred reductions, which the
    # CPU-backend partitioner materializes in f32 (2× wire).
    explicit_tp: bool = False

    @property
    def vocab_padded(self) -> int:
        m = self.vocab_pad_multiple
        return -(-self.vocab // m) * m

    @property
    def grng(self) -> GRNGConfig:
        return GRNGConfig()

    def head_bayes_cfg(self) -> BayesDenseConfig:
        return BayesDenseConfig(
            d_in=self.d_model, d_out=self.vocab_padded,
            sigma_init=self.sigma_init, prior_sigma=self.prior_sigma,
            grng=self.grng)

    def param_count(self) -> int:
        """Analytic parameter count (embedding + blocks + head µ+ρ)."""
        d, f, l = self.d_model, self.d_ff, self.n_layers
        hq = self.n_heads * self.head_dim
        hkv = self.n_kv_heads * self.head_dim
        attn_p = d * hq + 2 * d * hkv + hq * d
        if self.n_experts:
            mlp_p = self.n_experts * 3 * d * f + d * self.n_experts
        else:
            mlp_p = 3 * d * f if self.mlp == "swiglu" else 2 * d * f
        per_layer = attn_p + mlp_p + 2 * d
        total = l * per_layer + self.vocab_padded * d * 2
        if self.encoder_layers:
            total += self.encoder_layers * (attn_p + mlp_p + 2 * d)
            total += l * (attn_p + d)          # decoder xattn blocks
        if self.cross_attn_every:
            n_cross = l // self.cross_attn_every
            total += n_cross * (attn_p + mlp_p + 2 * d)
        return total

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if not self.n_experts:
            return self.param_count()
        d, f, l = self.d_model, self.d_ff, self.n_layers
        hq = self.n_heads * self.head_dim
        hkv = self.n_kv_heads * self.head_dim
        attn_p = d * hq + 2 * d * hkv + hq * d
        mlp_p = self.top_k * 3 * d * f + d * self.n_experts
        return l * (attn_p + mlp_p + 2 * d) + self.vocab_padded * d * 2


def _remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return "none"
    if cfg.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _maybe_remat(fn, cfg: ModelConfig):
    policy = _remat_policy(cfg)
    if policy == "none":
        return fn
    return jax.checkpoint(fn, policy=policy)


def _wsc(x, cfg: ModelConfig, *rest):
    """Constrain leading batch dim to the DP axes (launcher-provided)."""
    if not cfg.batch_axes:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(tuple(cfg.batch_axes), *rest))


def _model_ax(cfg: ModelConfig, dim: int):
    """'model' when the launcher told us the axis size divides ``dim``."""
    if cfg.model_axis_size and dim % cfg.model_axis_size == 0:
        return "model"
    return None


def _tp_ok(cfg: ModelConfig, d_in: int, d_out: int) -> bool:
    if not (cfg.explicit_tp and cfg.batch_axes and cfg.model_axis_size > 1):
        return False
    mesh = jax.sharding.get_abstract_mesh()
    data = mesh.shape.get("data", 1)
    return d_out % cfg.model_axis_size == 0 and d_in % data == 0


def _tp_linear(x, w, cfg: ModelConfig, kind: str):
    """Explicit tensor-parallel matmul (Megatron row/col parallel).

    'col': w [D_in(fsdp:data), D_out(tp:model)] — no fwd collective, the
           bwd dgrad psum is emitted by shard_map's transpose in x.dtype.
    'row': w [D_in(tp:model), D_out(fsdp:data)] — ONE fwd psum in
           x.dtype (bf16), the whole point: the GSPMD partitioner on the
           CPU backend reduces these partials in f32.
    FSDP gathers of w over 'data' are explicit; their transpose is the
    reduce-scatter of the weight gradient.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    dp = tuple(cfg.batch_axes)
    lead = (dp,) + (None,) * (x.ndim - 2)

    if kind == "col":
        def body(x_loc, w_loc):
            w_full = lax.all_gather(w_loc, "data", axis=0, tiled=True)
            return x_loc @ w_full.astype(x_loc.dtype)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(*lead, None), P("data", "model")),
            out_specs=P(*lead, "model"), check_vma=False)(x, w)

    def body(x_loc, w_loc):
        w_full = lax.all_gather(w_loc, "data", axis=1, tiled=True)
        y = x_loc @ w_full.astype(x_loc.dtype)
        return lax.psum(y, "model")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(*lead, "model"), P("model", "data")),
        out_specs=P(*lead, None), check_vma=False)(x, w)


# ----------------------------------------------------------------------
# Parameter init
# ----------------------------------------------------------------------
def _init_attn_block(key, cfg: ModelConfig, l: int, cross: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim
    dt = jnp.float32
    p = {
        "wq": jax.vmap(lambda k: blocks.dense_init(k, d, hq, dt))(
            jax.random.split(ks[0], l)),
        "wk": jax.vmap(lambda k: blocks.dense_init(k, d, hkv, dt))(
            jax.random.split(ks[1], l)),
        "wv": jax.vmap(lambda k: blocks.dense_init(k, d, hkv, dt))(
            jax.random.split(ks[2], l)),
        "wo": jax.vmap(lambda k: blocks.dense_init(k, hq, d, dt))(
            jax.random.split(ks[3], l)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((l, hq), dt)
        p["bk"] = jnp.zeros((l, hkv), dt)
        p["bv"] = jnp.zeros((l, hkv), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((l, cfg.head_dim), dt)
        p["k_norm"] = jnp.ones((l, cfg.head_dim), dt)
    return p


def _init_mlp_block(key, cfg: ModelConfig, l: int) -> dict:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.float32
    if cfg.mlp == "swiglu":
        return {
            "wi": jax.vmap(lambda k: blocks.dense_init(k, d, f, dt))(
                jax.random.split(ks[0], l)),
            "wg": jax.vmap(lambda k: blocks.dense_init(k, d, f, dt))(
                jax.random.split(ks[1], l)),
            "wo": jax.vmap(lambda k: blocks.dense_init(k, f, d, dt))(
                jax.random.split(ks[2], l)),
        }
    return {
        "wi": jax.vmap(lambda k: blocks.dense_init(k, d, f, dt))(
            jax.random.split(ks[0], l)),
        "bi": jnp.zeros((l, f), dt),
        "wo": jax.vmap(lambda k: blocks.dense_init(k, f, d, dt))(
            jax.random.split(ks[1], l)),
        "bo": jnp.zeros((l, d), dt),
    }


def _init_block_stack(key, cfg: ModelConfig, l: int, cross: bool = False,
                      with_xattn: bool = False) -> dict:
    ka, km, kx = jax.random.split(key, 3)
    p = {
        "attn": _init_attn_block(ka, cfg, l, cross),
        "ln1": jnp.ones((l, cfg.d_model), jnp.float32),
        "ln2": jnp.ones((l, cfg.d_model), jnp.float32),
    }
    if cfg.norm == "ln":
        p["ln1_b"] = jnp.zeros((l, cfg.d_model), jnp.float32)
        p["ln2_b"] = jnp.zeros((l, cfg.d_model), jnp.float32)
    if cfg.n_experts and not cross:
        p["moe"] = init_moe(km, l, cfg.d_model, cfg.d_ff, cfg.n_experts)
    else:
        p["mlp"] = _init_mlp_block(km, cfg, l)
    if with_xattn:  # enc-dec decoder layer: self → cross → mlp
        p["xattn"] = _init_attn_block(kx, cfg, l, cross=True)
        p["lnx"] = jnp.ones((l, cfg.d_model), jnp.float32)
        if cfg.norm == "ln":
            p["lnx_b"] = jnp.zeros((l, cfg.d_model), jnp.float32)
    return p


def init_transformer(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    params: dict = {
        "embed": blocks.embed_init(keys[0], cfg.vocab_padded, cfg.d_model),
        "blocks": _init_block_stack(keys[1], cfg, cfg.n_layers,
                                    with_xattn=cfg.encoder_layers > 0),
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.norm == "ln":
        params["final_norm_b"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if cfg.learned_pos:
        params["pos_embed"] = (
            jax.random.normal(keys[2], (cfg.learned_pos, cfg.d_model)) * 0.02)
    if cfg.encoder_layers:
        params["encoder"] = {
            "blocks": _init_block_stack(keys[3], cfg, cfg.encoder_layers),
            "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
            "final_norm_b": jnp.zeros((cfg.d_model,), jnp.float32),
            "pos_embed": (jax.random.normal(keys[4], (cfg.n_frames, cfg.d_model))
                          * 0.02),
        }
    if cfg.cross_attn_every:
        n_cross = cfg.n_layers // cfg.cross_attn_every
        params["blocks_cross"] = _init_block_stack(keys[5], cfg, n_cross,
                                                   cross=True)
    if cfg.bayesian_head:
        params["head"] = bayes_layer.init(keys[6], cfg.head_bayes_cfg())
    else:
        params["head"] = {"w": blocks.dense_init(
            keys[6], cfg.d_model, cfg.vocab_padded)}
    return params


# ----------------------------------------------------------------------
# Block applications
# ----------------------------------------------------------------------
def _norm(h, scale, bias, cfg: ModelConfig):
    if cfg.norm == "ln":
        return blocks.layer_norm(h, scale, bias)
    return blocks.rms_norm(h, scale)


def _project_qkv(h, p, cfg: ModelConfig, memory=None):
    """Returns q [B,S,Hq,dh], k,v [B,Skv,Hkv,dh] (memory for cross-attn)."""
    src = h if memory is None else memory
    hq_dim = cfg.n_heads * cfg.head_dim
    if _tp_ok(cfg, h.shape[-1], hq_dim):
        q = _tp_linear(h, p["wq"], cfg, "col")
    else:
        q = h @ p["wq"].astype(h.dtype)
    k = src @ p["wk"].astype(h.dtype)
    v = src @ p["wv"].astype(h.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(h.dtype)
        k = k + p["bk"].astype(h.dtype)
        v = v + p["bv"].astype(h.dtype)
    b, s = q.shape[:2]
    skv = k.shape[1]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, skv, cfg.n_kv_heads, cfg.head_dim)
    q = _wsc(q, cfg, None, _model_ax(cfg, cfg.n_heads), None)
    k = _wsc(k, cfg, None, _model_ax(cfg, cfg.n_kv_heads), None)
    v = _wsc(v, cfg, None, _model_ax(cfg, cfg.n_kv_heads), None)
    if cfg.qk_norm and "q_norm" in p:
        q = blocks.rms_norm(q, p["q_norm"])
        k = blocks.rms_norm(k, p["k_norm"])
    return q, k, v


def _mlp_apply(h, lp, cfg: ModelConfig):
    if "moe" in lp:
        # Manual local dispatch pays one FSDP weight-gather per call —
        # amortized over 1M training tokens, ruinous for single-token
        # decode (S=1): there the GSPMD path with TP-sharded weights
        # moves only activations.
        if cfg.batch_axes and cfg.model_axis_size > 1 and h.shape[1] > 1:
            # Perf I1: manual local dispatch - routing is batch-parallel,
            # so no dispatch collectives; one TP psum + FSDP gathers only.
            from repro.models.moe import make_sharded_moe
            moe = make_sharded_moe(
                jax.sharding.get_abstract_mesh(), top_k=cfg.top_k,
                capacity_factor=cfg.capacity_factor,
                n_experts=cfg.n_experts, dp_axes=tuple(cfg.batch_axes))
            return moe(h, lp["moe"]["router"].astype(h.dtype),
                       lp["moe"]["wi"].astype(h.dtype),
                       lp["moe"]["wg"].astype(h.dtype),
                       lp["moe"]["wo"].astype(h.dtype))
        ep = ("model" if (cfg.model_axis_size
                          and cfg.n_experts % cfg.model_axis_size == 0)
              else None)
        y, aux = moe_apply(h, lp["moe"]["router"].astype(h.dtype),
                           lp["moe"]["wi"].astype(h.dtype),
                           lp["moe"]["wg"].astype(h.dtype),
                           lp["moe"]["wo"].astype(h.dtype),
                           top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor,
                           ep_axis=ep)
        return y, aux
    p = lp["mlp"]
    if cfg.mlp == "swiglu":
        if _tp_ok(cfg, h.shape[-1], cfg.d_ff) and _tp_ok(
                cfg, cfg.d_ff, p["wo"].shape[1]):
            hi = jax.nn.silu(_tp_linear(h, p["wg"], cfg, "col")) * _tp_linear(
                h, p["wi"], cfg, "col")
            y = _tp_linear(hi, p["wo"], cfg, "row")
        else:
            y = blocks.swiglu(h, p["wi"].astype(h.dtype),
                              p["wg"].astype(h.dtype), p["wo"].astype(h.dtype))
    else:
        y = blocks.gelu_mlp(h, p["wi"].astype(h.dtype), p["bi"].astype(h.dtype),
                            p["wo"].astype(h.dtype), p["bo"].astype(h.dtype))
    return y, jnp.zeros((), jnp.float32)


def _xattn_full(h, lp, cfg: ModelConfig, memory):
    """Cross-attention sub-block (full sequence). Returns (delta, (xk, xv))."""
    hn = _norm(h, lp["lnx"], lp.get("lnx_b"), cfg)
    q, k, v = _project_qkv(hn, lp["xattn"], cfg, memory=memory)
    o = attn.chunked_attention(q, attn.expand_kv(k, cfg.n_heads),
                               attn.expand_kv(v, cfg.n_heads), causal=False,
                               chunk_q=cfg.attn_chunk_q,
                               chunk_kv=cfg.attn_chunk_kv)
    return o.reshape(*h.shape[:2], -1) @ lp["xattn"]["wo"].astype(h.dtype), (k, v)


def _block_full(h, lp, cfg: ModelConfig, positions, causal: bool, memory=None,
                kv_start=None):
    """One block: self-attn [→ cross-attn] → mlp. Returns (h, aux, caches)."""
    hn = _norm(h, lp["ln1"], lp.get("ln1_b"), cfg)
    q, k, v = _project_qkv(hn, lp["attn"], cfg)
    if cfg.use_rope:
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
    ke = _wsc(attn.expand_kv(k, cfg.n_heads), cfg, None,
              _model_ax(cfg, cfg.n_heads), None)
    ve = _wsc(attn.expand_kv(v, cfg.n_heads), cfg, None,
              _model_ax(cfg, cfg.n_heads), None)
    o = attn.chunked_attention(
        q, ke, ve, causal=causal, window=cfg.swa_window, kv_start=kv_start,
        chunk_q=cfg.attn_chunk_q, chunk_kv=cfg.attn_chunk_kv)
    o = _wsc(o, cfg, None, _model_ax(cfg, cfg.n_heads), None)
    of = o.reshape(*h.shape[:2], -1)
    if _tp_ok(cfg, lp["attn"]["wo"].shape[1], of.shape[-1]):
        h = h + _tp_linear(of, lp["attn"]["wo"], cfg, "row")
    else:
        h = h + of @ lp["attn"]["wo"].astype(h.dtype)
    h = _wsc(h, cfg, None, None)
    xkv = None
    if "xattn" in lp:
        delta, xkv = _xattn_full(h, lp, cfg, memory)
        h = h + delta
    hn = _norm(h, lp["ln2"], lp.get("ln2_b"), cfg)
    y, aux = _mlp_apply(hn, lp, cfg)
    return _wsc(h + y, cfg, None, None), aux, (k, v), xkv


def _block_decode(h, lp, cfg: ModelConfig, ck, cv, pos, rolling, xk=None,
                  xv=None, start=None):
    """Single-token block against KV cache (+ optional cross memory kv)."""
    hn = _norm(h, lp["ln1"], lp.get("ln1_b"), cfg)
    q, k, v = _project_qkv(hn, lp["attn"], cfg)
    if cfg.use_rope:
        positions = jnp.full((h.shape[0], 1), pos, jnp.int32)
        q = blocks.apply_rope(q, positions, cfg.rope_theta)
        k = blocks.apply_rope(k, positions, cfg.rope_theta)
    ck, cv = attn.cache_update(ck, cv, k, v, pos, rolling=rolling)
    o = attn.decode_attention(q, ck, cv, pos,
                              window=cfg.swa_window, rolling=rolling,
                              start=start)
    h = h + o.reshape(*h.shape[:2], -1) @ lp["attn"]["wo"].astype(h.dtype)
    if "xattn" in lp:
        hn = _norm(h, lp["lnx"], lp.get("lnx_b"), cfg)
        qx = (hn @ lp["xattn"]["wq"].astype(h.dtype)).reshape(
            h.shape[0], 1, cfg.n_heads, cfg.head_dim)
        ox = attn.decode_attention(qx, xk, xv, jnp.int32(xk.shape[1] - 1))
        h = h + ox.reshape(*h.shape[:2], -1) @ lp["xattn"]["wo"].astype(h.dtype)
    hn = _norm(h, lp["ln2"], lp.get("ln2_b"), cfg)
    y, _ = _mlp_apply(hn, lp, cfg)
    return h + y, ck, cv


def _cross_layer_full(h, lp, cfg: ModelConfig, memory):
    """Dedicated VLM cross-attention layer (own MLP, llama-3.2 style)."""
    hn = _norm(h, lp["ln1"], lp.get("ln1_b"), cfg)
    q, k, v = _project_qkv(hn, lp["attn"], cfg, memory=memory)
    o = attn.chunked_attention(q, attn.expand_kv(k, cfg.n_heads),
                               attn.expand_kv(v, cfg.n_heads), causal=False,
                               chunk_q=cfg.attn_chunk_q,
                               chunk_kv=cfg.attn_chunk_kv)
    h = h + o.reshape(*h.shape[:2], -1) @ lp["attn"]["wo"].astype(h.dtype)
    hn = _norm(h, lp["ln2"], lp.get("ln2_b"), cfg)
    y, aux = _mlp_apply(hn, lp, cfg)
    return h + y, aux, (k, v)


def _cross_layer_decode(h, lp, cfg: ModelConfig, xk, xv):
    hn = _norm(h, lp["ln1"], lp.get("ln1_b"), cfg)
    q = (hn @ lp["attn"]["wq"].astype(h.dtype)).reshape(
        h.shape[0], 1, cfg.n_heads, cfg.head_dim)
    o = attn.decode_attention(q, xk, xv, jnp.int32(xk.shape[1] - 1))
    h = h + o.reshape(*h.shape[:2], -1) @ lp["attn"]["wo"].astype(h.dtype)
    hn = _norm(h, lp["ln2"], lp.get("ln2_b"), cfg)
    y, _ = _mlp_apply(hn, lp, cfg)
    return h + y


# ----------------------------------------------------------------------
# Trunk forward
# ----------------------------------------------------------------------
def _encode(params, frames, cfg: ModelConfig):
    enc = params["encoder"]
    eh = frames.astype(cfg.dtype) + enc["pos_embed"].astype(cfg.dtype)[None]
    epos = jnp.broadcast_to(jnp.arange(frames.shape[1], dtype=jnp.int32)[None],
                            frames.shape[:2])

    def body(h, lp):
        h, aux, _, _ = _block_full(h, lp, cfg, epos, causal=False)
        return h, aux

    body = _maybe_remat(body, cfg)
    eh, _ = lax.scan(body, eh, enc["blocks"])
    return blocks.layer_norm(eh, enc["final_norm"], enc["final_norm_b"])


def trunk_forward(params, tokens, cfg: ModelConfig, *, frames=None,
                  image_embeds=None, collect_cache: bool = False,
                  kv_start=None):
    """Token trunk -> (hidden [B,S,D], aux, caches dict|None, memory).

    kv_start: optional [B] first-valid positions for left-padded rows
    (continuous-batching admission) — masks self-attention only.
    """
    b, s = tokens.shape
    h = _wsc(params["embed"].astype(cfg.dtype)[tokens], cfg, None, None)
    if cfg.learned_pos:
        h = h + params["pos_embed"][:s].astype(cfg.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    memory = None
    if cfg.encoder_layers:
        assert frames is not None, "whisper needs stub frame embeddings"
        memory = _encode(params, frames, cfg)
    if cfg.cross_attn_every:
        assert image_embeds is not None, "vlm needs stub patch embeddings"
        memory = image_embeds.astype(cfg.dtype)

    def self_body(h, lp):
        h, aux, kv, xkv = _block_full(h, lp, cfg, positions, causal=True,
                                      memory=memory, kv_start=kv_start)
        outs = (aux, kv if collect_cache else None,
                xkv if (collect_cache and xkv is not None) else None)
        return h, outs

    self_body_r = _maybe_remat(self_body, cfg)
    caches: dict | None = {} if collect_cache else None

    if cfg.cross_attn_every and "blocks_cross" in params:
        every = cfg.cross_attn_every
        n_groups = params["blocks_cross"]["ln1"].shape[0]
        grouped = jax.tree.map(
            lambda x: x.reshape(n_groups, every, *x.shape[1:]),
            params["blocks"])

        def cross_body(h, lp):
            h, aux, xkv = _cross_layer_full(h, lp, cfg, memory)
            return h, (aux, xkv if collect_cache else None)

        cross_body_r = _maybe_remat(cross_body, cfg)

        def group_fn(h, xs):
            gself, glp = xs
            h, (aux, kvs, _) = lax.scan(self_body_r, h, gself)
            h, (aux_c, xkv) = cross_body_r(h, glp)
            return h, (aux.sum() + aux_c, kvs, xkv)

        h, (aux, kvs, xkvs) = lax.scan(group_fn, h,
                                       (grouped, params["blocks_cross"]))
        aux = aux.sum()
        if collect_cache:
            k, v = kvs  # [G, E, B, S, Hkv, dh]
            caches["k"] = k.reshape(-1, *k.shape[2:])
            caches["v"] = v.reshape(-1, *v.shape[2:])
            caches["xk"], caches["xv"] = xkvs  # [G, B, Sm, Hkv, dh]
    else:
        h, (aux, kvs, xkvs) = lax.scan(self_body_r, h, params["blocks"])
        aux = aux.sum()
        if collect_cache:
            caches["k"], caches["v"] = kvs
            if cfg.encoder_layers:
                caches["xk"], caches["xv"] = xkvs

    if cfg.norm == "ln":
        h = blocks.layer_norm(h, params["final_norm"], params["final_norm_b"])
    else:
        h = blocks.rms_norm(h, params["final_norm"])
    return h, aux, caches, memory


# ----------------------------------------------------------------------
# Heads + losses
# ----------------------------------------------------------------------
def head_logits_train(params_head, h, cfg: ModelConfig, step):
    """Single reparameterized-sample logits + KL (Bayes-by-backprop)."""
    if not cfg.bayesian_head:
        return h @ params_head["w"].astype(h.dtype), jnp.zeros((), jnp.float32)
    bcfg = cfg.head_bayes_cfg()
    w = bayes_layer.sample_weights_at(params_head, bcfg, step)
    kl = bayes_layer.kl_divergence(params_head, bcfg)
    return h @ w.astype(h.dtype), kl


def train_loss(params, batch, cfg: ModelConfig, step=0):
    """Next-token CE + KL + MoE aux. batch: dict(tokens, labels, ...)."""
    h, aux, _, _ = trunk_forward(
        params, batch["tokens"], cfg,
        frames=batch.get("frames"), image_embeds=batch.get("image_embeds"))
    logits, kl = head_logits_train(params["head"], h, cfg, step)
    logits = _wsc(logits, cfg, None, _model_ax(cfg, cfg.vocab_padded))
    ce = blocks.causal_cross_entropy(logits, batch["labels"], cfg.vocab)
    n_tokens = batch["tokens"].shape[0] * batch["tokens"].shape[1]
    loss = ce + cfg.kl_weight * kl / n_tokens + cfg.aux_loss_weight * aux
    return loss, {"ce": ce, "kl": kl, "aux": aux}


# ----------------------------------------------------------------------
# Serving
# ----------------------------------------------------------------------
def prefill(params, tokens, cfg: ModelConfig, *, cache_len: int,
            frames=None, image_embeds=None, prompt_lengths=None):
    """Run the prompt, build KV caches sized ``cache_len``.

    Returns (cache dict, last-position hidden [B, D]).  SWA models whose
    cache_len exceeds the window get a rolling cache of size window.

    ``prompt_lengths`` [B]: true prompt lengths of LEFT-padded rows —
    the continuous-batching admission path (serving/engine.py).  Pad
    positions are masked out of attention here and recorded as a per-
    slot ``start`` in the cache so decode keeps masking them.  Exact for
    RoPE trunks: a slot's tokens shift uniformly, and RoPE scores depend
    only on relative distance.
    """
    b, s = tokens.shape
    rolling = cfg.swa_window is not None and cache_len > cfg.swa_window
    sc = min(cache_len, cfg.swa_window) if rolling else cache_len
    kv_start = None
    if prompt_lengths is not None:
        if rolling:
            raise ValueError(
                "prompt_lengths (left-padded admission) is not supported "
                f"with a rolling SWA cache (cache_len={cache_len} > "
                f"window={cfg.swa_window}): decode_attention cannot apply "
                "the per-slot start mask to a circular buffer")
        kv_start = (s - prompt_lengths).astype(jnp.int32)    # [B]
    h, _, caches, _ = trunk_forward(
        params, tokens, cfg, frames=frames, image_embeds=image_embeds,
        collect_cache=True, kv_start=kv_start)

    def fit(x):  # [L, B, S, Hkv, dh] -> [L, B, sc, Hkv, dh]
        if s >= sc:
            return x[:, :, s - sc:]
        return jnp.pad(x, ((0, 0), (0, 0), (0, sc - s), (0, 0), (0, 0)))

    cache = {"k": fit(caches["k"]), "v": fit(caches["v"]),
             "pos": jnp.int32(s)}
    if prompt_lengths is not None:
        # Front-truncated prompt (s > sc, linear cache): the valid
        # region shifts with the truncation.
        cache["start"] = kv_start if s <= sc else jnp.maximum(
            kv_start - (s - sc), 0)
    if "xk" in caches:
        cache["xk"], cache["xv"] = caches["xk"], caches["xv"]
    return cache, h[:, -1]


def _head_serving(params, cfg: ModelConfig):
    """Serving head params: prepared {mu_prime, sigma} or raw fallback."""
    hp = params["head"]
    if "mu_prime" in hp:
        return {"mu_prime": hp["mu_prime"].astype(cfg.dtype),
                "sigma": hp["sigma"].astype(cfg.dtype)}
    from repro.core.bayes_layer import sigma_of
    return {"mu_prime": hp["mu"].astype(cfg.dtype),
            "sigma": sigma_of(hp).astype(cfg.dtype)}


def decode_hidden(params, cache, token, cfg: ModelConfig):
    """One trunk decode step WITHOUT the Bayesian head.

    token: [B,1] -> (last hidden [B, D], new cache).  The serving engine
    uses this split so it can sample the head *adaptively* — a small
    first draw, then escalations — instead of a fixed R fused into the
    step (serving/adaptive.py).  ``decode_step`` composes this with
    ``apply_bayes_head`` and is unchanged in behavior.

    Honors ``cache['start']`` ([B] first-valid positions) written by
    prefill for left-padded continuous-batching admissions.
    """
    pos = cache["pos"]
    start = cache.get("start")
    h = params["embed"].astype(cfg.dtype)[token]             # [B, 1, D]
    if cfg.learned_pos:
        pe = lax.dynamic_slice_in_dim(params["pos_embed"], pos, 1, axis=0)
        h = h + pe.astype(cfg.dtype)[None, 0:1, 0]

    rolling = (cfg.swa_window is not None
               and cache["k"].shape[2] <= cfg.swa_window)

    if cfg.cross_attn_every and "blocks_cross" in params:
        every = cfg.cross_attn_every
        n_groups = params["blocks_cross"]["ln1"].shape[0]
        grouped = jax.tree.map(
            lambda x: x.reshape(n_groups, every, *x.shape[1:]),
            params["blocks"])
        kg = cache["k"].reshape(n_groups, every, *cache["k"].shape[1:])
        vg = cache["v"].reshape(n_groups, every, *cache["v"].shape[1:])

        def self_body(h, xs):
            lp, ck, cv = xs
            h, ck, cv = _block_decode(h, lp, cfg, ck, cv, pos, rolling,
                                      start=start)
            return h, (ck, cv)

        def group_body(h, xs):
            gself, ck, cv, glp, xk, xv = xs
            h, (ck, cv) = lax.scan(self_body, h, (gself, ck, cv))
            h = _cross_layer_decode(h, glp, cfg, xk, xv)
            return h, (ck, cv)

        h, (ck, cv) = lax.scan(
            group_body, h, (grouped, kg, vg, params["blocks_cross"],
                            cache["xk"], cache["xv"]))
        new_cache = dict(cache, k=ck.reshape(-1, *ck.shape[2:]),
                         v=cv.reshape(-1, *cv.shape[2:]), pos=pos + 1)
    elif cfg.encoder_layers:
        def body(h, xs):
            lp, ck, cv, xk, xv = xs
            h, ck, cv = _block_decode(h, lp, cfg, ck, cv, pos, rolling,
                                      xk=xk, xv=xv, start=start)
            return h, (ck, cv)

        h, (ck, cv) = lax.scan(body, h, (params["blocks"], cache["k"],
                                         cache["v"], cache["xk"],
                                         cache["xv"]))
        new_cache = dict(cache, k=ck, v=cv, pos=pos + 1)
    else:
        def body(h, xs):
            lp, ck, cv = xs
            h, ck, cv = _block_decode(h, lp, cfg, ck, cv, pos, rolling,
                                      start=start)
            return h, (ck, cv)

        h, (ck, cv) = lax.scan(body, h, (params["blocks"], cache["k"],
                                         cache["v"]))
        new_cache = dict(cache, k=ck, v=cv, pos=pos + 1)

    if cfg.norm == "ln":
        h = blocks.layer_norm(h, params["final_norm"], params["final_norm_b"])
    else:
        h = blocks.rms_norm(h, params["final_norm"])
    return h[:, 0], new_cache                                # [B, D]


def decode_step(params, cache, token, cfg: ModelConfig):
    """One decode step. token: [B,1] -> (logit_samples [R,B,Vp], cache).

    The selection stream is indexed by decode position (write-free
    random access — see lfsr.indexed_selections) so every generated
    token sees fresh CLT-GRNG samples, as the hardware's free-running
    LFSR would provide.
    """
    pos = cache["pos"]
    x, new_cache = decode_hidden(params, cache, token, cfg)
    return apply_bayes_head(params, x, cfg, pos), new_cache


def apply_bayes_head(params, x, cfg: ModelConfig, pos):
    """R logit samples from the Bayesian head at decode position ``pos``."""
    from repro.core.sampling import BayesHeadConfig, logit_samples
    if not cfg.bayesian_head:
        return (x @ params["head"]["w"].astype(x.dtype))[None]
    hcfg = BayesHeadConfig(num_samples=cfg.uq_samples, mode=cfg.head_mode,
                           grng=cfg.grng, compute_dtype=cfg.dtype)
    head = _head_serving(params, cfg)
    idx = (jnp.asarray(pos, jnp.uint32) * jnp.uint32(cfg.uq_samples)
           + jnp.arange(cfg.uq_samples, dtype=jnp.uint32))
    sel = indexed_selections(cfg.grng.lfsr_seed, idx)
    return logit_samples(head, x, hcfg, sel=sel)
