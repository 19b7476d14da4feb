"""Plain float32 reference of SAR triage serving, independent of ``src/``.

It restates, in straight ``jax.numpy`` with no kernels, batching or
caches, what a served request computes (arXiv:2606.10822 §V-B and the
repo's serving semantics) on an ideal die:

* the CLT-GRNG: hashed virtual device currents, the swapper selection
  network, random-access selection states;
* the deployment transform of the Bayesian head: closed-form static
  offset compensation and the hoisted rank-16 sigma basis;
* the conv trunk;
* the activation basis, the per-sample logits, and the running triage
  statistics and three-way verdict after every escalation round.

Contractions go through a ``dot`` policy so that the reference states
the arithmetic the configuration states: ``dot_f32`` is float32 at
"highest"; ``dot_default`` rounds both operands to bfloat16 first, which
is what XLA's and Mosaic's default contraction precision does with
float32 operands on a TPU (one bf16 pass, float32 accumulation).
``dot_bf16`` also rounds the result to bfloat16: the control.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
ACCEPT, ESCALATE, FLAG = 0, 1, 2


# ----------------------------------------------------------------------
# contraction policies
# ----------------------------------------------------------------------
def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def dot_f32(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def dot_default(eq, a, b):
    return jnp.einsum(eq, _bf16(a), _bf16(b), precision=HIGHEST)


def dot_bf16(eq, a, b):
    return _bf16(dot_default(eq, a, b))


def conv(x, w, dot, stride=2):
    """VALID stride-2 NHWC/HWIO convolution under a contraction policy."""
    if dot is dot_f32:
        xa, wa = x, w
    else:
        xa, wa = _bf16(x), _bf16(w)
    y = lax.conv_general_dilated(xa, wa, (stride, stride), "VALID",
                                 dimension_numbers=("NHWC", "HWIO", "NHWC"),
                                 precision=HIGHEST)
    return _bf16(y) if dot is dot_bf16 else y


# ----------------------------------------------------------------------
# hashing and the selection network
# ----------------------------------------------------------------------
def mix32(x):
    x = jnp.asarray(x, jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hash3(k, n, j, seed):
    k, n, j = (jnp.asarray(v, jnp.uint32) for v in (k, n, j))
    h = mix32(j * jnp.uint32(0xC2B2AE35) + jnp.uint32(seed))
    h = mix32(n * jnp.uint32(0x85EBCA6B) + h)
    return mix32(k * jnp.uint32(0x9E3779B9) + h)


def uniform_bit(h):
    return ((h >> jnp.uint32(31)) & jnp.uint32(1)).astype(jnp.float32)


def gaussianish(h):
    b0 = (h & jnp.uint32(0xFF)).astype(jnp.float32)
    b1 = ((h >> jnp.uint32(8)) & jnp.uint32(0xFF)).astype(jnp.float32)
    b2 = ((h >> jnp.uint32(16)) & jnp.uint32(0xFF)).astype(jnp.float32)
    return (b0 + b1 + b2 - 382.5) * (1.0 / 127.99316)


def swapper_select(state):
    """16-bit control word -> selection vector with exactly eight ones."""
    state = jnp.asarray(state, jnp.uint32)
    bits = jnp.arange(8, dtype=jnp.uint32)
    c1 = ((state[..., None] >> bits) & 1).astype(jnp.float32)
    c2 = ((state[..., None] >> (8 + bits)) & 1).astype(jnp.float32)
    v = jnp.broadcast_to(jnp.asarray([1.0, 0.0] * 8), state.shape + (16,))
    pairs = v.reshape(state.shape + (8, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    v1 = jnp.stack([a + c1 * (b - a), b + c1 * (a - b)],
                   axis=-1).reshape(state.shape + (16,))
    lo, hi = v1[..., :8], v1[..., 8:]
    return jnp.concatenate([lo + c2 * (hi - lo), hi + c2 * (lo - hi)], -1)


def indexed_selections(seed, idx):
    """Selection vectors at absolute stream positions ``idx``."""
    h = mix32(jnp.asarray(idx, jnp.uint32) * jnp.uint32(0x9E3779B9)
              + jnp.uint32(seed))
    s = h & jnp.uint32(0xFFFF)
    return swapper_select(jnp.where(s == 0, jnp.uint32(0xACE1), s))


@dataclasses.dataclass(frozen=True)
class Grng:
    """Device-current model and standardization of one die's GRNG."""
    i_lo: float = 0.926
    delta_i: float = 0.673
    gamma: float = 0.100
    sum_mean: float = 10.1
    sum_std: float = 0.993
    seed: int = 0xC1A0
    lfsr_seed: int = 0xACE1

    @classmethod
    def from_config(cls, grng: dict) -> "Grng":
        """The configuration's ``model.grng``; the reference's selection
        network is 8 of 16 devices, one selection per layer."""
        shape = (grng["n_devices"], grng["k_select"], grng["granularity"])
        if shape != (16, 8, "layer"):
            raise ValueError(f"GRNG {shape}: the reference draws 8 of 16 "
                             "devices per layer")
        return cls(**{f.name: grng[f.name]
                      for f in dataclasses.fields(cls)})


def device_currents(g: Grng, n_rows: int, n_cols: int):
    rows = jnp.arange(n_rows, dtype=jnp.uint32)[:, None, None]
    cols = jnp.arange(n_cols, dtype=jnp.uint32)[None, :, None]
    h = hash3(rows, cols, jnp.arange(16, dtype=jnp.uint32), g.seed)
    return g.i_lo + g.delta_i * uniform_bit(h) + g.gamma * gaussianish(h)


# ----------------------------------------------------------------------
# the Bayesian head's deployment
# ----------------------------------------------------------------------
def deploy_head(head: dict, g: Grng):
    """The serving head: offset-compensated mu', sigma and the basis."""
    mu = head["mu"]
    sigma = jax.nn.softplus(head["rho"])
    k, n = mu.shape
    cur = device_currents(g, k, n)
    d_eps = (cur.sum(-1) * 0.5 - g.sum_mean) / g.sum_std
    basis = sigma[..., None] * cur
    return {"mu_prime": mu - sigma * d_eps, "sigma": sigma, "basis": basis}


# ----------------------------------------------------------------------
# the trunk
# ----------------------------------------------------------------------
def trunk_ideal(params, images, dot):
    h = images
    for layer in params["convs"]:
        h = jax.nn.relu(conv(h, layer["w"], dot) + layer["b"])
    return h.mean(axis=(1, 2))


# ----------------------------------------------------------------------
# basis, samples, statistics, verdicts
# ----------------------------------------------------------------------
def activation_basis(head, x, dot):
    ab = {"y_mu": dot("bk,kn->bn", x, head["mu_prime"]),
          "x_sigma": dot("bk,kn->bn", x, head["sigma"]),
          "m": dot("bk,knj->bnj", x, head["basis"])}
    return ab


def trajectories(ab, g: Grng, base, r_max: int, r_step: int,
                 policy: dict, mix_precision=HIGHEST):
    """Finalized statistics and verdict of every request after each of
    its escalation rounds.  base: [B] selection-stream base.
    Returns a dict of [B, r_max // r_step] arrays."""
    idx = (base[:, None] + jnp.arange(r_max, dtype=jnp.uint32)[None, :])
    sel = indexed_selections(g.lfsr_seed, idx)                  # [B,R,16]
    mix = jnp.einsum("brj,bnj->brn", sel, ab["m"], precision=mix_precision)
    out = mix - g.sum_mean * ab["x_sigma"][:, None]
    logits = ab["y_mu"][:, None] + out / g.sum_std
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    ent = -(p * logp).sum(-1)                                   # [B,R]
    rounds = r_max // r_step

    def cum(x):      # running sums after each round: [B, rounds, ...]
        c = jnp.cumsum(x, axis=1)
        return c[:, r_step - 1::r_step]

    n = (jnp.arange(1, rounds + 1) * r_step).astype(jnp.float32)[None]
    sum_p, sum_psq = cum(p), cum(p * p)
    sum_ent, sum_entsq = cum(ent), cum(ent * ent)
    p_mean = sum_p / n[..., None]
    pred = p_mean.argmax(-1)
    conf = p_mean.max(-1)
    pred_ent = -(p_mean * jnp.log(jnp.maximum(p_mean, 1e-12))).sum(-1)
    exp_ent = sum_ent / n
    take = lambda a: jnp.take_along_axis(a, pred[..., None], -1)[..., 0]
    p_pred, psq_pred = take(sum_p) / n, take(sum_psq) / n
    conf_se = jnp.sqrt(jnp.maximum(psq_pred - p_pred ** 2, 0.0) / n)
    mi = pred_ent - exp_ent
    mi_se = jnp.sqrt(jnp.maximum(sum_entsq / n - exp_ent ** 2, 0.0) / n)
    z = policy["z"]
    tc, tm = policy["conf_threshold"], policy["mi_threshold"]
    cs, ms = z * conf_se, z * mi_se
    accept_c = (conf - cs >= tc) & (mi + ms <= tm)
    flag_c = (conf + cs < tc) | (mi - ms > tm)
    verdict = jnp.where(flag_c, FLAG, jnp.where(accept_c, ACCEPT, ESCALATE))
    forced = jnp.where((conf >= tc) & (mi <= tm), ACCEPT, FLAG)
    final = n >= r_max
    verdict = jnp.where(final & (verdict == ESCALATE), forced, verdict)
    # distance of the statistics from the nearest decision boundary
    margin = jnp.min(jnp.abs(jnp.stack(
        [conf - cs - tc, conf + cs - tc, conf - tc,
         mi + ms - tm, mi - ms - tm, mi - tm])), axis=0)
    top2 = jnp.sort(p_mean, axis=-1)[..., -2:]
    return {"verdict": verdict, "confidence": conf, "prediction": pred,
            "mutual_information": mi, "margin": margin,
            "pred_margin": top2[..., 1] - top2[..., 0]}
