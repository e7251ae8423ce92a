"""Sharded FlexAI engine: scheduled-tasks/sec vs forced host device count.

The scan engine is embarrassingly parallel over routes, so the shard_map
variant should scale until the per-device lane width stops covering the
scan-step overhead.  On the CPU backend each measurement runs in a
subprocess, because ``--xla_force_host_platform_device_count`` must be set
before jax imports; on a chip it runs in this process over the first
device counts of ``jax.devices()`` (``common.run_device_arm``).

Every child also replays the same batch through the plain single-device
vmapped scan and checks fp32 parity (identical placements, metrics within
fp32 tolerance) — the multi-device engine must be a pure re-layout.

Emits the standard benchmark rows *and* ``BENCH_sharded_engine.json``
(repo root) with the 1->4 device scaling factor.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEVICE_COUNTS = (1, 2, 4)
RESULT_TAG = "SHARDED_RESULT "


def _child_main(args) -> None:
    """One device-count arm: a forced-device child on the CPU backend,
    in-process over the first ``args.devices`` chips elsewhere."""
    import time

    import jax
    import numpy as np

    from benchmarks.common import RATE_SCALE, arm_devices
    from repro.compat import make_mesh
    from repro.core.environment import EnvironmentParams, build_task_queue
    from repro.core.flexai import (FlexAIAgent, FlexAIConfig,
                                   make_schedule_fn,
                                   make_sharded_schedule_fn)
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import spec_from_platform, summarize
    from repro.core.tasks import (TaskArrays, pad_route_batch,
                                  pad_task_arrays, stack_task_arrays,
                                  tasks_to_arrays)

    n_dev = arm_devices(args.devices)

    # a few unique routes, tiled out to the lane count (same math, cheap
    # host-side queue generation)
    uniq = []
    for s in range(args.unique_routes):
        q = build_task_queue(EnvironmentParams(
            route_km=0.05, rate_scale=RATE_SCALE, seed=300 + s,
            max_times_turn=2, max_times_reverse=1,
            max_duration_turn=4.0, max_duration_reverse=6.0))
        ta = pad_task_arrays(tasks_to_arrays(q), max(len(q), args.tasks))
        uniq.append(TaskArrays(*[np.asarray(f)[: args.tasks] for f in ta]))
    routes = [uniq[i % len(uniq)] for i in range(args.lanes)]
    batch = pad_route_batch(stack_task_arrays(routes), n_dev)

    plat = HMAIPlatform(capacity_scale=RATE_SCALE)
    spec = spec_from_platform(plat)
    params = FlexAIAgent(plat, FlexAIConfig(seed=13)).learner.eval_p

    def best_of(fn, iters):
        """Min over iters: the shared CI host is noisy and best-of is the
        standard way to read the machine's actual capability."""
        result = fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            result = fn()
            best = min(best, time.perf_counter() - t0)
        return result, best

    mesh = make_mesh((n_dev,), ("routes",))
    sharded = make_sharded_schedule_fn(spec, mesh)
    out, t_sharded = best_of(
        lambda: jax.block_until_ready(sharded(params, batch)), args.iters)
    n_tasks = int(np.asarray(batch.valid).sum())
    tps = n_tasks / t_sharded

    # fp32 parity vs the single-device scan path (plain vmapped jit runs
    # on device 0 regardless of the forced device count)
    plain = make_schedule_fn(spec, batched=True)
    ref, t_plain = best_of(
        lambda: jax.block_until_ready(plain(params, batch)), args.iters)
    f_sh, r_sh = jax.device_get(out)
    f_pl, r_pl = jax.device_get(ref)
    placements_equal = bool(
        np.array_equal(np.asarray(r_sh.action), np.asarray(r_pl.action)))
    metric_diff = 0.0
    for lane in range(args.lanes):
        s_sh = summarize(spec, *jax.tree_util.tree_map(
            lambda a, l=lane: a[l], (f_sh, r_sh)))
        s_pl = summarize(spec, *jax.tree_util.tree_map(
            lambda a, l=lane: a[l], (f_pl, r_pl)))
        for k in ("stm_rate", "gvalue", "makespan_s", "total_energy_j"):
            denom = max(abs(s_pl[k]), 1e-9)
            metric_diff = max(metric_diff,
                              abs(s_sh[k] - s_pl[k]) / denom)
    assert metric_diff < 1e-4, f"sharded/plain divergence {metric_diff}"
    assert placements_equal, "sharded placements diverge from the " \
        "single-device scan path"

    print(RESULT_TAG + json.dumps({
        "devices": n_dev,
        "lanes": int(batch.arrival.shape[0]),
        "tasks_per_lane": args.tasks,
        "scheduled_tasks_per_s": round(tps, 1),
        "plain_single_device_tasks_per_s": round(n_tasks / t_plain, 1),
        "placements_equal": placements_equal,
        "metric_rel_diff_max": metric_diff,
    }))


def _spawn(devices: int, lanes: int, tasks: int, iters: int,
           unique_routes: int) -> dict:
    from benchmarks.common import run_device_arm
    return run_device_arm(
        "sharded_engine", devices,
        ["--lanes", lanes, "--tasks", tasks, "--iters", iters,
         "--unique-routes", unique_routes],
        RESULT_TAG)


def run(quick: bool = True) -> list:
    from benchmarks.common import host_tuning, row, save

    # wide lanes: per-step compute must dominate the scan-step overhead for
    # route sharding to pay (at width <=32 the engine is overhead-bound and
    # extra devices only add contention — measured on the 2-core CI host)
    lanes = 256 if quick else 512
    tasks = 256 if quick else 512
    iters = 5
    results = {d: _spawn(d, lanes, tasks, iters, unique_routes=8)
               for d in DEVICE_COUNTS}
    tps = {d: r["scheduled_tasks_per_s"] for d, r in results.items()}
    scaling = round(tps[4] / tps[1], 2)

    summary = {
        "lanes": lanes,
        "tasks_per_lane": tasks,
        "by_device_count": results,
        "scaling_4dev_over_1dev": scaling,
        "parity_fp32_ok": all(r["metric_rel_diff_max"] < 1e-4
                              for r in results.values()),
        "placements_equal": all(r["placements_equal"]
                                for r in results.values()),
    }
    summary["host_tuning"] = host_tuning(devices=4)
    with open(os.path.join(os.getcwd(), "BENCH_sharded_engine.json"),
              "w") as f:
        json.dump(summary, f, indent=1)

    rows = [
        row(f"sharded_engine/{d}dev", 1e6 / tps[d],
            f"{tps[d]:.0f} tasks/s") for d in DEVICE_COUNTS
    ]
    rows.append(row("sharded_engine/scaling_4dev_over_1dev", 0.0, scaling))
    rows.append(row("sharded_engine/parity_fp32_ok", 0.0,
                    summary["parity_fp32_ok"]))
    save("sharded_engine", rows)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--lanes", type=int, default=256)
    ap.add_argument("--tasks", type=int, default=256)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--unique-routes", type=int, default=8)
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        _child_main(args)
        return 0
    for r in run(quick=not args.full):
        print(r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
