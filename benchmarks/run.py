"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Modules:
  table1_comparison  Table I   accelerator metrics (derived vs paper)
  fig2_overhead      Fig. 2    BNN energy overhead vs R
  fig9_distribution  Fig. 9/10 GRNG distribution quality + selection net
  sec5a_energy       SecV-A    tile energy/latency/endurance breakdown
  fig16_uq           Fig.16    SARD accuracy + UQ (CNN vs BNN vs CLT)
  table2_corr        Fig.17/II corruption robustness
  kernel_bench       --        rank16-vs-paper FLOP scaling, kernels
  serving_bench      --        adaptive-R vs fixed-R serving engine
  fleet_bench        --        mesh-of-pools fleet scaling sweep
                               (BENCH_fleet, 8 simulated devices)
  hw_variation       --        chip-instance MC sweep, cal vs uncal
  mission_bench      --        closed-loop SAR mission (BENCH_mission)
  lifetime_bench     --        FeFET aging + self-healing redeploy
                               (BENCH_lifetime)
  roofline           --        decision-path roofline (always) +
                               3-term roofline over dry-run artifacts

Run:   PYTHONPATH=src python -m benchmarks.run [--only <m>] [--fast|--all]
(or:   PYTHONPATH=src python benchmarks/run.py ... — both entry forms
register the whole suite).  The default run skips nothing but honours
historical behaviour; ``--fast`` skips the model-training benches,
``--all`` forces every registered module even under ``--fast``.

Every module's rows are also appended as one schema-versioned record
(git SHA + backend fingerprint) to repo-root ``BENCH_history.jsonl``
(benchmarks/history.py); ``--no-history`` suppresses that.
"""

from __future__ import annotations

import argparse
import sys
import traceback
from pathlib import Path

if __package__ in (None, ""):                    # `python benchmarks/run.py`
    _root = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(_root))
    sys.path.insert(0, str(_root / "src"))       # repro.* without PYTHONPATH

MODULES = [
    "table1_comparison",
    "fig2_overhead",
    "fig9_distribution",
    "sec5a_energy",
    "kernel_bench",
    "serving_bench",
    "slo_bench",
    "fleet_bench",
    "hw_variation",
    "fig16_uq",
    "table2_corr",
    "mission_bench",
    "lifetime_bench",
    "roofline",
]
FAST_SKIP = {"fig16_uq", "table2_corr", "serving_bench",
             "slo_bench", "fleet_bench", "hw_variation",
             "mission_bench", "lifetime_bench"}  # SAR training


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=MODULES)
    ap.add_argument("--fast", action="store_true",
                    help="skip benchmarks that train models")
    ap.add_argument("--all", action="store_true",
                    help="run every registered module (overrides --fast)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip appending run records to "
                         "BENCH_history.jsonl")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = []
    for mod_name in MODULES:
        if args.only and mod_name != args.only:
            continue
        if args.fast and not args.all and mod_name in FAST_SKIP:
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["bench"])
            rows = list(mod.bench())
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
            if not args.no_history:
                from benchmarks import history
                history.record_rows(mod_name, rows)
        except Exception:  # noqa: BLE001
            failures.append(mod_name)
            traceback.print_exc()
    if failures:
        print(f"# FAILED modules: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
