"""Synthetic SARD crops and the detector's training, made on the device.

A copy of the scene arithmetic of ``src/repro/data/sard.py`` (terrain
clutter, a rock distractor, an elongated victim blob, sensor noise, fog)
so that the benchmark's inputs and weights do not depend on the program
under test.  The detector is trained here too, with the benchmark's own
loss and optimizer: 250 AdamW steps (lr 1e-3, weight decay 0.01, betas
0.9/0.95, clip 1.0) at batch 64 on SARD seed 7 from model key 3, the
recipe of ``benchmarks/serving_bench.py``, with the configuration's
GRNG.  Both are one jitted call.

The trained parameters of each configuration are kept as data,
``bench/weights/<config>.npz``, so that a run loads them instead of
training.  They are made on the CPU, where float32 contractions are
exact, and remade with

    JAX_PLATFORMS=cpu python bench/sard.py <config>

which ``bench/tests`` also does, to check the file against its recipe.
"""

from __future__ import annotations

import json
import sys
from functools import partial
from pathlib import Path

if __name__ == "__main__":
    sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

VICTIM, DISTRACTOR, CLUTTER = 2.4, 1.3, 0.8
ALTITUDE = (0.6, 1.4)


def _smooth_noise(key, n, octaves=3):
    img = jnp.zeros((n, n))
    for o in range(octaves):
        k = jax.random.fold_in(key, o)
        size = max(2, n // (2 ** (octaves - o)))
        coarse = jax.random.normal(k, (size, size))
        img = img + jax.image.resize(coarse, (n, n), "bilinear") / (2 ** o)
    return img


def _blob(n, cy, cx, sy, sx, theta):
    y = jnp.arange(n)[:, None] - cy
    x = jnp.arange(n)[None, :] - cx
    ct, st = jnp.cos(theta), jnp.sin(theta)
    u = ct * y + st * x
    v = -st * y + ct * x
    return jnp.exp(-0.5 * ((u / sy) ** 2 + (v / sx) ** 2))


def make_image(key, has_victim, n: int):
    """One [n, n, 1] crop: a pure function of ``key`` and the label."""
    ks = jax.random.split(key, 10)
    img = CLUTTER * _smooth_noise(ks[0], n)
    alt = jax.random.uniform(ks[1], (), minval=ALTITUDE[0],
                             maxval=ALTITUDE[1])
    dc = jax.random.uniform(ks[2], (2,), minval=4.0, maxval=n - 4.0)
    img = img + DISTRACTOR * _blob(n, dc[0], dc[1], 1.5 / alt, 1.5 / alt,
                                   0.0)
    vc = jax.random.uniform(ks[3], (2,), minval=4.0, maxval=n - 4.0)
    theta = jax.random.uniform(ks[4], (), maxval=np.pi)
    img = img + has_victim * VICTIM * _blob(n, vc[0], vc[1], 2.5 / alt,
                                            1.0 / alt, theta)
    img = img + 0.1 * jax.random.normal(ks[5], (n, n))
    return img[..., None]


def make_batch(key, batch: int, n: int):
    kl, ki = jax.random.split(key)
    labels = jax.random.permutation(
        kl, (jnp.arange(batch) % 2).astype(jnp.int32))
    images = jax.vmap(lambda k, y: make_image(k, y.astype(jnp.float32), n))(
        jax.random.split(ki, batch), labels)
    return images, labels


def fog(images, severity):
    haze = 0.7 * severity
    return images * (1 - haze) + haze * 1.2


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def image_bank(key, n_images: int, n: int, fog_share: float,
               fog_severity: float):
    """[n_images, n, n, 1] serving crops drawn from ``key`` (made from the
    run's seed): clean, with the first ``fog_share`` of every 32 fogged,
    the serving stream's mix."""
    images, _ = make_batch(key, n_images, n)
    n_fog = int(round(32 * fog_share))
    fogged = (jnp.arange(n_images) % 32) < n_fog
    return jnp.where(fogged[:, None, None, None],
                     fog(images, fog_severity), images)


def _init(key, cfg):
    """The program's parameter layout: convs [{w, b}], head {mu, rho}."""
    keys = jax.random.split(key, len(cfg["channels"]) + 1)
    convs, c_in = [], 1
    for i, c_out in enumerate(cfg["channels"]):
        scale = 1.0 / jnp.sqrt(float(cfg["kernel"] ** 2 * c_in))
        convs.append({"w": jax.random.normal(
            keys[i], (cfg["kernel"], cfg["kernel"], c_in, c_out)) * scale,
            "b": jnp.zeros((c_out,))})
        c_in = c_out
    kmu, _ = jax.random.split(keys[-1])
    d_in, d_out = cfg["channels"][-1], cfg["n_classes"]
    mu = jax.random.normal(kmu, (d_in, d_out)) / jnp.sqrt(float(d_in))
    rho = jnp.full((d_in, d_out), float(np.log(np.expm1(cfg["sigma_init"]))))
    return {"convs": convs, "head": {"mu": mu, "rho": rho}}


def _loss(params, images, labels, step, cfg, g):
    feats = ref.trunk_ideal(params, images, ref.dot_f32)
    mu, sigma = params["head"]["mu"], jax.nn.softplus(params["head"]["rho"])
    sel = ref.indexed_selections(g.lfsr_seed, step.astype(jnp.uint32))
    cur = ref.device_currents(g, *mu.shape)
    eps = ((cur * sel).sum(-1) - g.sum_mean) / g.sum_std
    w = mu + sigma * jax.lax.stop_gradient(eps)
    logits = feats @ w
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), labels[:, None],
                              axis=1).mean()
    sp = cfg["prior_sigma"]
    kl = (jnp.log(sp / sigma) + (sigma ** 2 + mu ** 2) / (2 * sp ** 2)
          - 0.5).sum()
    return ce + cfg["kl_weight"] * kl / images.shape[0]


def recipe_of(cfg: dict) -> str:
    """The training recipe of a configuration, as canonical JSON: its
    ``training`` settings and the model sizes and GRNG they train."""
    keys = ("image_size", "channels", "kernel", "n_classes", "sigma_init",
            "prior_sigma", "kl_weight", "grng")
    return json.dumps(dict(cfg["training"],
                           **{k: cfg["model"][k] for k in keys}),
                      sort_keys=True)


@partial(jax.jit, static_argnums=(0,))
def train(recipe: str):
    """Trained parameters from a recipe (``recipe_of``; no ``--seed``)."""
    cfg = json.loads(recipe)
    g = ref.Grng.from_config(cfg["grng"])
    params = _init(jax.random.PRNGKey(cfg["model_key"]), cfg)
    zeros = jax.tree.map(jnp.zeros_like, params)
    data_key = jax.random.PRNGKey(cfg["data_seed"])
    b1, b2, lr, wd = 0.9, 0.95, cfg["lr"], cfg["weight_decay"]

    def step(carry, s):
        p, m, v = carry
        images, labels = make_batch(jax.random.fold_in(data_key, s),
                                    cfg["batch"], cfg["image_size"])
        grads = jax.grad(_loss)(p, images, labels, s, cfg, g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, 1.0 / (gnorm + 1e-9))
        t = (s + 1).astype(jnp.float32)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x * scale, m, grads)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * (x * scale) ** 2,
                         v, grads)
        p = jax.tree.map(
            lambda w, a, c: w - lr * ((a / (1 - b1 ** t))
                                      / (jnp.sqrt(c / (1 - b2 ** t)) + 1e-8)
                                      + wd * w), p, m, v)
        return (p, m, v), None

    (params, _, _), _ = jax.lax.scan(step, (params, zeros, zeros),
                                     jnp.arange(cfg["steps"]))
    return params


WEIGHTS = Path(__file__).resolve().parent / "weights"


def save_params(path: Path, params, recipe: str) -> None:
    flat = {f"convs.{i}.{k}": np.asarray(v)
            for i, layer in enumerate(params["convs"])
            for k, v in layer.items()}
    flat.update({f"head.{k}": np.asarray(v)
                 for k, v in params["head"].items()})
    with open(path, "wb") as f:
        np.savez(f, recipe=np.asarray(recipe), **flat)


def load_params(path: Path, recipe: str):
    """The parameters kept in ``path``; they must have been trained from
    ``recipe``."""
    with np.load(path) as z:
        if str(z["recipe"]) != recipe:
            raise ValueError(f"{path} was trained from another recipe; "
                             "remake it: JAX_PLATFORMS=cpu python "
                             "bench/sard.py <config>")
        n = len([k for k in z.files if k.endswith(".w")])
        return {"convs": [{"w": z[f"convs.{i}.w"], "b": z[f"convs.{i}.b"]}
                          for i in range(n)],
                "head": {"mu": z["head.mu"], "rho": z["head.rho"]}}


def main(argv=None) -> int:
    """Train a configuration's detector and write its weights file."""
    import argparse
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("config", help="name of bench/configs/<config>.json")
    ap.add_argument("--out", type=Path,
                    help="where to write (default bench/weights/<config>.npz)")
    args = ap.parse_args(argv)
    with open(WEIGHTS.parent / "configs" / f"{args.config}.json") as f:
        cfg = json.load(f)
    recipe = recipe_of(cfg)
    out = args.out or WEIGHTS / f"{args.config}.npz"
    out.parent.mkdir(parents=True, exist_ok=True)
    save_params(out, jax.device_get(train(recipe)), recipe)
    print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
