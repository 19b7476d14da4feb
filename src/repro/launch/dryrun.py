import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, WITHOUT allocating any model-sized buffer:
  * a compiled SPMD executable for the production mesh (16×16 single pod
    / 2×16×16 multi-pod) — sharding mismatches, compile-time OOM and
    unsupported collectives all fail loudly here;
  * compiled.memory_analysis()  — proves the per-device footprint fits;
  * compiled.cost_analysis()    — per-device HLO FLOPs/bytes;
  * a parse of the post-SPMD HLO summing wire bytes of every collective
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) — the roofline's collective term.

Results land in artifacts/dryrun/<arch>__<shape>__<mesh>.json, consumed
by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k [--multi-pod] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cells_for, get_config
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (jit_decode_step, jit_prefill_step,
                                jit_train_step)
from repro.obs.log import get_logger
from repro.optim import AdamWConfig

log = get_logger("dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"= .*?\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _line_bytes(line: str, op: str) -> tuple[int, int]:
    """(result_bytes, operand_bytes) for one HLO instruction line."""
    idx = line.find(op)
    head, tail = line[:idx], line[idx:]
    res = sum(_shape_bytes(m.group(1), m.group(2))
              for m in _SHAPE_RE.finditer(head)
              if m.group(1) in _DTYPE_BYTES)
    ops = sum(_shape_bytes(m.group(1), m.group(2))
              for m in _SHAPE_RE.finditer(tail)
              if m.group(1) in _DTYPE_BYTES)
    return res, ops


def _group_size(line: str, total_devices: int) -> int:
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return total_devices


def parse_collectives(hlo_text: str, total_devices: int) -> dict:
    """Per-device wire-byte model per collective type.

    ring estimates: AR 2(g-1)/g·s, AG/RS (g-1)/g·full, A2A (g-1)/g·s,
    permute s.  (s = max(result, operand) bytes on the line.)
    """
    out: dict = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        op = m.group(1)
        res, opd = _line_bytes(line, m.group(0).split("= ")[-1] if "= " in m.group(0) else op)
        size = max(res, opd)
        g = _group_size(line, total_devices)
        if g <= 1:
            wire = 0.0
        elif op == "all-reduce":
            wire = 2.0 * (g - 1) / g * size
        elif op == "collective-permute":
            wire = float(size)
        else:  # all-gather / reduce-scatter / all-to-all
            wire = (g - 1) / g * size
        rec = out.setdefault(op, {"count": 0, "wire_bytes": 0.0,
                                  "payload_bytes": 0.0})
        rec["count"] += 1
        rec["wire_bytes"] += wire
        rec["payload_bytes"] += size
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path, serve_r: int | None = None,
             head_mode: str | None = None, tag: str = "",
             master_weights: bool = False, microbatches: int = 1,
             explicit_tp: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    overrides = {}
    if serve_r is not None:
        overrides["uq_samples"] = serve_r
    if head_mode is not None:
        overrides["head_mode"] = head_mode
    if explicit_tp:
        overrides["explicit_tp"] = True
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.perf_counter()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            jitted, abstracts, _, cfg2 = jit_train_step(
                cfg, mesh, AdamWConfig(master_weights=master_weights),
                shape.seq_len, shape.global_batch,
                microbatches=microbatches)
        elif shape.kind == "prefill":
            jitted, abstracts, _, cfg2 = jit_prefill_step(
                cfg, mesh, shape.seq_len, shape.global_batch)
        else:
            jitted, abstracts, _, cfg2 = jit_decode_step(
                cfg, mesh, shape.seq_len, shape.global_batch)
        lowered = jitted.lower(*abstracts)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    loop_aware = hlo_analyze(hlo, n_dev)   # trip-count-corrected
    colls = loop_aware["collectives"]
    log.debug(str(compiled.memory_analysis()))
    log.debug(str({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed", "optimal_seconds")}))

    # Useful-FLOP accounting (global, whole step).
    n_tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        model_flops = 6 * cfg2.active_param_count() * n_tokens
    elif shape.kind == "prefill":
        model_flops = 2 * cfg2.active_param_count() * n_tokens
    else:  # decode: one token per sequence; R head samples
        head_flops = 2 * cfg2.d_model * cfg2.vocab_padded
        r_eff = cfg2.uq_samples if cfg2.head_mode == "paper" else min(
            cfg2.uq_samples, 17)
        model_flops = (2 * cfg2.active_param_count()
                       + (r_eff + 1) * head_flops) * shape.global_batch

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "devices": n_dev,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "uq_samples": cfg2.uq_samples, "head_mode": cfg2.head_mode,
        "param_count": cfg2.param_count(),
        "active_param_count": cfg2.active_param_count(),
        "model_flops_global": float(model_flops),
        "flops_per_device": loop_aware["flops_per_device"],
        "hbm_bytes_per_device": loop_aware["hbm_bytes_per_device"],
        "xla_flops_uncorrected": float(cost.get("flops", -1)),
        "xla_bytes_uncorrected": float(cost.get("bytes accessed", -1)),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes),
        },
        "collectives": colls,
        "wire_bytes_per_device": sum(c["wire_bytes"] for c in colls.values()),
        "wire_bytes_per_device_tpu": loop_aware["wire_bytes_per_device_tpu"],
        "wire_bytes_f32_per_device": loop_aware["wire_bytes_f32_per_device"],
        "hlo_bytes": len(hlo),
        "lower_s": t_lower, "compile_s": t_compile,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = out_dir / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    path.write_text(json.dumps(result, indent=2))
    log.info(f"OK {arch} × {shape_name} × {mesh_name}"
             f" (lower {t_lower:.1f}s, compile {t_compile:.1f}s)"
             f" -> {path}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--serve-r", type=int, default=None,
                    help="override uq_samples (hillclimb sweeps)")
    ap.add_argument("--head-mode", default=None,
                    choices=("paper", "rank16", "moment"))
    ap.add_argument("--tag", default="", help="suffix for output file")
    ap.add_argument("--master-weights", action="store_true")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--explicit-tp", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.all:
        failures = []
        for arch in ARCHS:
            for shape_name in cells_for(arch):
                mesh_name = "pod2x16x16" if args.multi_pod else "pod16x16"
                path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
                if args.skip_existing and path.exists():
                    log.info(f"skip existing {path.name}")
                    continue
                try:
                    run_cell(arch, shape_name, args.multi_pod, out_dir)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape_name, repr(e)))
                    traceback.print_exc()
        if failures:
            log.error(f"{len(failures)} FAILURES:")
            for f in failures:
                log.error(f"  {f}")
            raise SystemExit(1)
        log.info("all cells OK")
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        run_cell(args.arch, args.shape, args.multi_pod, out_dir,
                 serve_r=args.serve_r, head_mode=args.head_mode,
                 tag=args.tag, master_weights=args.master_weights,
                 microbatches=args.microbatch, explicit_tp=args.explicit_tp)


if __name__ == "__main__":
    main()
