"""Training driver: real data-parallel training with fault tolerance.

This is the launcher the examples use.  It runs any registered arch
(reduced or full config) on whatever devices exist, with:

  * stateless data pipeline (exact resume from any step),
  * async checksummed checkpointing + atomic publish (repro.ckpt),
  * straggler monitor feeding the metrics stream,
  * optional int8+error-feedback gradient compression,
  * optional simulated failure (--fail-at) to exercise restart: rerun
    the same command and it resumes from the last checkpoint.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ck [--fail-at 60]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.ckpt import AsyncCheckpointer, latest_step, restore
from repro.configs import ARCHS, get_config
from repro.data.tokens import TokenPipelineConfig, batch_at, stub_frames, \
    stub_image_embeds
from repro.launch.mesh import make_debug_mesh
from repro.launch.steps import make_train_step, mesh_hinted_config
from repro.models.registry import get_api
from repro.optim import AdamWConfig, init_opt_state
from repro.optim.compression import compressed_gradients, init_error_state
from repro.runtime import StragglerConfig, StragglerMonitor
from repro.sharding import specs as S


def build_batch(cfg, pipe_cfg, step):
    batch = batch_at(pipe_cfg, step)
    if cfg.family == "audio":
        batch["frames"] = stub_frames(pipe_cfg, cfg.n_frames, cfg.d_model,
                                      step, pipe_cfg.global_batch)
    if cfg.family == "vlm":
        batch["image_embeds"] = stub_image_embeds(
            pipe_cfg, cfg.n_image_tokens, cfg.d_model, step,
            pipe_cfg.global_batch)
    return batch


def train(arch: str, *, smoke: bool = True, steps: int = 100, batch: int = 8,
          seq: int = 64, ckpt_dir: str | None = None, ckpt_every: int = 50,
          fail_at: int | None = None, compress: bool = False,
          lr: float = 3e-4, log_every: int = 10,
          metrics_path: str | None = None) -> dict:
    cfg = get_config(arch, smoke=smoke)
    mesh = make_debug_mesh()
    cfg = mesh_hinted_config(cfg, mesh, batch)
    api = get_api(cfg)
    opt_cfg = AdamWConfig(lr=lr)
    pipe_cfg = TokenPipelineConfig(vocab=cfg.vocab, seq_len=seq,
                                   global_batch=batch)

    base_step = make_train_step(cfg, opt_cfg, total_steps=steps,
                                warmup_steps=max(1, steps // 20))

    if compress:
        def step_fn(params, opt_state, err, batch_):
            def loss_fn(p):
                return api.train_loss(p, batch_, cfg,
                                      step=opt_state["count"])
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            grads, err = compressed_gradients(grads, err)
            from repro.optim import adamw_update, warmup_cosine
            lr_scale = warmup_cosine(opt_state["count"],
                                     warmup_steps=max(1, steps // 20),
                                     total_steps=steps)
            params, opt_state, om = adamw_update(params, grads, opt_state,
                                                 opt_cfg, lr_scale)
            return params, opt_state, err, dict(metrics, loss=loss, **om)
        jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))
    else:
        jitted = jax.jit(base_step, donate_argnums=(0, 1))

    # --- init or resume -------------------------------------------------
    start = 0
    err_state = None
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        tree, start = restore(ckpt_dir)
        params, opt_state = tree["params"], tree["opt"]
        params = jax.tree.map(jnp.asarray, params)
        opt_state = jax.tree.map(jnp.asarray, opt_state)
        print(f"[train] resumed from step {start}")
    else:
        params = api.init(jax.random.PRNGKey(0), cfg)
        opt_state = init_opt_state(params)
    if compress:
        err_state = init_error_state(params)

    ckptr = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
    monitor = StragglerMonitor(StragglerConfig())
    metrics_file = open(metrics_path, "a") if metrics_path else None
    history = []

    with jax.set_mesh(mesh):
        for step in range(start, steps):
            monitor.start_step()
            data = build_batch(cfg, pipe_cfg, step)
            if compress:
                params, opt_state, err_state, metrics = jitted(
                    params, opt_state, err_state, data)
            else:
                params, opt_state, metrics = jitted(params, opt_state, data)
            metrics = {k: float(v) for k, v in metrics.items()}
            report = monitor.end_step(step)
            metrics["step_time"] = report["duration"]
            history.append({"step": step, **metrics})
            if metrics_file:
                metrics_file.write(json.dumps(history[-1]) + "\n")
                metrics_file.flush()
            if step % log_every == 0:
                print(f"[train] step {step} loss={metrics['loss']:.4f} "
                      f"ce={metrics['ce']:.4f} t={report['duration']:.2f}s")
            if ckptr and (step + 1) % ckpt_every == 0:
                ckptr.submit(step + 1, {"params": params, "opt": opt_state})
            if fail_at is not None and step + 1 == fail_at:
                if ckptr:
                    ckptr.wait()
                raise SystemExit(f"[train] simulated failure at step {step+1}")

    if ckptr:
        ckptr.submit(steps, {"params": params, "opt": opt_state})
        ckptr.wait()
    if metrics_file:
        metrics_file.close()
    return {"params": params, "opt": opt_state, "history": history,
            "final_loss": history[-1]["loss"] if history else None}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--metrics", default=None)
    args = ap.parse_args()
    out = train(args.arch, smoke=args.smoke, steps=args.steps,
                batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
                ckpt_every=args.ckpt_every, fail_at=args.fail_at,
                compress=args.compress, lr=args.lr,
                metrics_path=args.metrics)
    print(f"[train] done, final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
