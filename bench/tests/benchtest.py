"""Helpers of the benchmark's own tests (CPU, small sizes)."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# a mix small enough for a test run: two vehicles at 5% of the camera
# rates, 1 s windows (about 80 tasks, bucket 128)
SMALL_MIX = {
    "arrivals": "closed_backlog",
    "window_s": 1.0,
    "rate_scale": 0.05,
    "route": {"route_km": 0.03, "max_times_turn": 2, "max_times_reverse": 1,
              "max_duration_turn": 4.0, "max_duration_reverse": 5.0},
    "vehicles": [{"area": "UB", "route_seed": 11},
                 {"area": "HW", "route_seed": 12}],
}


def small_registry(tmp_path, mix=None, name="small"):
    """A copy of ``bench/`` under ``tmp_path`` whose ``BENCHMARK.json``
    holds one cell ``name`` of the shipped configuration under a small
    mix."""
    from benchlib.registry import Registry
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (bench / "traffic" / f"{name}.json").write_text(
        json.dumps(mix or SMALL_MIX))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = spec["workloads"][0]["config"]
    spec["workloads"] = [{"name": name, "config": cfg, "traffic": name,
                          "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(root=tmp_path, bench=bench)


def run_small(tmp_path, seconds=0.5, control=False, mix=None, trace=0,
              registry=None):
    """Drive a whole run of the small cell on the CPU (of ``registry``,
    a :func:`small_registry`, where given); returns the result line."""
    import io

    import run as bench_run
    reg = registry or small_registry(tmp_path, mix=mix)
    args = bench_run.parse_args(
        ["--workload", "small", "--seed", str(2**33 + 5),
         "--seconds", str(seconds), "--trace", str(trace),
         "--control", str(int(control))])
    return bench_run.execute(args, registry=reg, require_chip=False,
                             use_cache=False, err=io.StringIO())
