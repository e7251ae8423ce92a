"""Benchmark runner: one module per paper table/figure + kernel micro.
Prints ``name,us_per_call,derived`` CSV per row and writes the
full JSON per module to experiments/bench/.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only MOD]
"""
from __future__ import annotations

import argparse
import sys
import time

MODULES = [
    "table1_cnn_features",
    "table5_fps_requirements",
    "table8_accelerator_perf",
    "fig2_platform_comparison",
    "fig10_hmai_vs_baselines",
    "fig11_training_loss",
    "fig12_scheduler_comparison",
    "fig13_stmrate",
    "fig14_braking_distance",
    "scheduler_throughput",
    "serve_qos",
    "serve_load",
    "metaheuristic_throughput",
    "sharded_engine",
    "training_throughput",
    "pipeline",
    "kernel_micro",
    "kernels",
    "recovery",
    "scenarios",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size queues / all areas (slow)")
    ap.add_argument("--only", default=None, help="run a single module")
    args = ap.parse_args(argv)
    quick = not args.full
    from repro.compat import enable_compile_cache
    enable_compile_cache()

    mods = [args.only] if args.only else MODULES
    print("name,us_per_call,derived")
    failures = 0
    for mod_name in mods:
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run(quick=quick)
            for r in rows:
                derived = str(r["derived"]).replace(",", ";")
                print(f"{r['name']},{r['us_per_call']},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{mod_name},0,ERROR:{type(e).__name__}:{e}",
                  file=sys.stderr)
        print(f"# {mod_name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
