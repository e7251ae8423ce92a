"""Pipeline parallelism over the heterogeneous mesh (ISSUE 7 tentpole).

One deep perception route becomes a stage DAG; stages are placed on
accelerator *groups* (``core.pipeline.build_stage_plan``) and executed as
a micro-batched wavefront, either flattened on one device or stage-sharded
over a 2-D ``("stages", "routes")`` mesh with ``lax.ppermute`` resharding
at every stage boundary.

The contract this module gates (CI reads ``BENCH_pipeline.json``):

* **makespan**: on a drain workload (all tasks queued at t=0, deadlines
  waived) over deep UB routes, EFT placement with >= 2 stage groups must
  finish strictly earlier than single-stage placement over the SAME 11
  accelerators — pipelining wins by keeping each group busy on its own
  stage instead of serializing whole tasks.  Measured on the simulated
  platform clock (``makespan_s``), which is host-independent; wall times
  ride along as info on this oversubscribed CI host.
* **parity, flat vs reference**: the flattened wavefront engine must be
  bit-exact against the unpipelined task-major reference.
* **parity, sharded vs flat**: the shard_map'd engine on the (2, 2) mesh
  (4 forced host devices) must reproduce the flattened records and the
  combined final platform state bit-exactly — the mesh run is a pure
  re-layout.

Runs in a subprocess because ``--xla_force_host_platform_device_count``
must be set before jax imports.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

RESULT_TAG = "PIPELINE_RESULT "


def _child_main(args) -> None:
    import time

    import jax
    import numpy as np

    from benchmarks.common import RATE_SCALE, arm_devices
    from repro.core.environment import Area, EnvironmentParams, \
        build_task_queue
    from repro.core.hmai import HMAIPlatform
    from repro.core.pipeline import (build_stage_plan, combine_stage_states,
                                     make_pipeline_reference_fn,
                                     make_pipeline_schedule_fn,
                                     make_sharded_pipeline_fn,
                                     pipeline_summarize)
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import TaskArrays, stack_task_arrays, \
        tasks_to_arrays
    from repro.launch.mesh import make_platform_mesh

    n_dev = arm_devices(args.devices)
    S = args.stages

    def drain(ta: TaskArrays, tasks: int) -> TaskArrays:
        """First ``tasks`` rows as a drain workload: everything queued at
        t=0, deadlines waived — makespan is then a pure throughput
        measure of the placement."""
        ta = TaskArrays(*[np.asarray(f)[:tasks] for f in ta])
        return ta._replace(arrival=np.zeros_like(ta.arrival),
                           safety=np.full_like(ta.safety, 1e9))

    routes = []
    for s in range(args.routes):
        q = build_task_queue(EnvironmentParams(
            area=Area.UB, route_km=0.04, rate_scale=RATE_SCALE,
            seed=700 + s))
        assert len(q) >= args.tasks, (len(q), args.tasks)
        routes.append(drain(tasks_to_arrays(q), args.tasks))
    batch = stack_task_arrays(routes)

    plat = HMAIPlatform(capacity_scale=RATE_SCALE)
    spec = spec_from_platform(plat)

    def best_of(fn, iters):
        result = fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            result = fn()
            best = min(best, time.perf_counter() - t0)
        return result, best

    def mean_makespan(plan, final, recs):
        ms = []
        for lane in range(args.routes):
            f, r = jax.tree_util.tree_map(
                lambda a, l=lane: a[l], (final, recs))
            ms.append(pipeline_summarize(spec, f, r)["makespan_s"])
        return float(np.mean(ms))

    # single-stage baseline: same engine, S=1 (== the task-level scan
    # engine bit-exactly; tests/test_pipeline.py), every accelerator
    # eligible for every task
    plan1 = build_stage_plan(plat, 1)
    single = make_pipeline_schedule_fn(spec, plan1, policy="eft",
                                       batched=True)
    (f1, _, r1), t_single = best_of(
        lambda: jax.block_until_ready(single(None, batch)), args.iters)
    mk_single = mean_makespan(plan1, f1, r1)

    # pipelined: stage groups partition the same 11 accelerators
    planS = build_stage_plan(plat, S)
    flat = make_pipeline_schedule_fn(spec, planS, policy="eft",
                                     batched=True)
    (fS, _, rS), t_flat = best_of(
        lambda: jax.block_until_ready(flat(None, batch)), args.iters)
    mk_pipe = mean_makespan(planS, fS, rS)

    # parity 1: flattened wavefront == unpipelined task-major reference
    ref = jax.vmap(make_pipeline_reference_fn(spec, planS, policy="eft"),
                   in_axes=(None, 0))
    fR, _, rR = jax.jit(ref)(None, batch)
    flat_vs_ref = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves((fS, rS)),
                        jax.tree_util.tree_leaves((fR, rR))))

    # parity 2: stage-sharded mesh run == flattened (records and combined
    # final state bit-exact; ring hops via ppermute)
    mesh = make_platform_mesh(S, devices=n_dev)
    sharded = make_sharded_pipeline_fn(spec, planS, mesh, policy="eft")
    (stS, _, rcS), t_shard = best_of(
        lambda: jax.block_until_ready(sharded(None, batch)), args.iters)
    recs_ok = all(
        np.array_equal(np.asarray(a).transpose(1, 2, 0), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(rcS),
                        jax.tree_util.tree_leaves(rS)))
    comb = combine_stage_states(planS, stS)
    state_ok = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(comb),
                        jax.tree_util.tree_leaves(fS)))

    n_tasks = int(np.asarray(batch.valid).sum())
    print(RESULT_TAG + json.dumps({
        "devices": n_dev,
        "stages": S,
        "mesh_shape": [S, n_dev // S],
        "routes": args.routes,
        "tasks_per_route": args.tasks,
        "makespan_single_stage_s": round(mk_single, 4),
        "makespan_pipeline_s": round(mk_pipe, 4),
        "makespan_gain": round(mk_single / mk_pipe, 4),
        "pipeline_beats_single_stage": bool(mk_pipe < mk_single),
        "parity_flat_vs_reference": bool(flat_vs_ref),
        "parity_sharded_vs_flat": bool(recs_ok and state_ok),
        "wall_single_s": round(t_single, 4),
        "wall_flat_s": round(t_flat, 4),
        "wall_sharded_s": round(t_shard, 4),
        "scheduled_tasks_per_s_flat": round(n_tasks / t_flat, 1),
    }))


def _spawn(devices: int, stages: int, routes: int, tasks: int,
           iters: int) -> dict:
    from benchmarks.common import run_device_arm
    return run_device_arm(
        "pipeline", devices,
        ["--stages", stages, "--routes", routes, "--tasks", tasks,
         "--iters", iters],
        RESULT_TAG)


def run(quick: bool = True) -> list:
    from benchmarks.common import host_tuning, row, save

    tasks = 768 if quick else 2048
    res = _spawn(devices=4, stages=2, routes=2, tasks=tasks, iters=1)

    summary = {
        "child": res,
        "gate": {
            "pipeline_beats_single_stage":
                res["pipeline_beats_single_stage"],
            "parity_flat_vs_reference": res["parity_flat_vs_reference"],
            "parity_sharded_vs_flat": res["parity_sharded_vs_flat"],
        },
        "host_tuning": host_tuning(devices=4),
    }
    with open(os.path.join(os.getcwd(), "BENCH_pipeline.json"), "w") as f:
        json.dump(summary, f, indent=1)

    rows = [
        row("pipeline/makespan_single_stage", 0.0,
            f"{res['makespan_single_stage_s']:.2f} s"),
        row("pipeline/makespan_2stage", 0.0,
            f"{res['makespan_pipeline_s']:.2f} s"),
        row("pipeline/makespan_gain", 0.0, res["makespan_gain"],
            paper="stage groups must beat single-stage at equal devices"),
        row("pipeline/parity_flat_vs_reference", 0.0,
            res["parity_flat_vs_reference"]),
        row("pipeline/parity_sharded_vs_flat", 0.0,
            res["parity_sharded_vs_flat"]),
    ]
    save("pipeline", rows)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--routes", type=int, default=2)
    ap.add_argument("--tasks", type=int, default=768)
    ap.add_argument("--iters", type=int, default=1)
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
