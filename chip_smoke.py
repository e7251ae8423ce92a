#!/usr/bin/env python3
"""Smoke run of FlexAI placement serving on a TPU, through the launchers.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip path only

One chip runs five phases in this one process (a chip belongs to one
process at a time), each printing its numbers on lines of its own:

* device — platform, device kind and count, jax / jaxlib / libtpu versions;
* kernel — the compiled fused TD update (both variants) next to the XLA
  update, and one ``conv2d`` per dataflow next to ``conv2d_ref``, at
  engine shapes and a YOLO stage-512 layer (204 -> 409 channels, so each
  dataflow runs its multi-step grid), all f32 matmuls at "highest"
  precision;
* train — ``repro.launch.train`` with ``--flexai --td-kernel``: one
  full-rate UB episode on the compiled kernel, saving EvalNet weights;
* serve — ``repro.launch.serve``'s deadline-aware placement serving
  (``--placement --qos edf``) of 8 full-rate routes mixing UB, UHW and HW
  with those weights;
* correctness — every served route's placements replayed twice in NumPy:
  through the float64 ``HMAIPlatform``, and through ``platform_step``'s
  clock arithmetic in float32, operation for operation.  The engine's
  deadline verdicts must equal the float32 replay's exactly, and every
  task's float32 response must lie within the float32 rounding bound of
  the float64 one (a full-rate route backlogs its cores for minutes, and
  at such clock values float32 rounding moves a verdict often enough to
  shift a route's STM by 1e-2, so the float64 STM alone admits no fixed
  tolerance); one route's FlexAI segment rerun on the
  CPU backend (agreement share printed: the Q-net runs at "highest"
  precision on both, but f32 rounding that differs between backends may
  still flip a near-tie argmax); one matmul-free ATA ``scan_schedule``
  route required bit-equal on chip and CPU.

``--chips 4`` runs only the multi-chip path and what it is compared with:
QoS waves sharded over a 4-chip ``("routes",)`` mesh at the default
slots (one wave lane per chip, as ``launch.serve --shard`` builds it)
against the single-device engine on the same 8 mixed routes at a tenth of
the Table-5 rate (placements and ``serving_digest`` equal), and a few
data-parallel trainer steps sharded against unsharded.

The last line of standard output is ``{"ok": true, "device": {...}}``.  The
script exits non-zero, printing no such line, when JAX finds no TPU or any
phase fails.  The compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else in ``<repo>/.jax_cache``; trained weights go to
``experiments/flexai/`` (git-ignored).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

RATE_SCALE = 1.0          # the paper's full Table-5 camera rates
SERVE_ROUTES = 8
# the four-chip comparison serves the 8 routes twice (one chip, then four);
# at full rate one such run holds a v5e chip for ~5 min, host-bound on
# per-segment dispatch and record transfer, so it runs at a tenth of it
MESH_RATE_SCALE = 0.1
PRECISION = "highest"     # f32 matmul precision of the kernel phase
KERNEL_TOL = 1e-4         # max |kernel - reference| / max |reference|
DP_PARAM_TOL = 1e-3       # DP trainer: sharded vs unsharded parameters
WEIGHTS = ROOT / "experiments" / "flexai" / "chip_smoke_ub.npz"


def _rel_err(out, ref) -> float:
    import jax
    import numpy as np
    err = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(out),
                    jax.tree_util.tree_leaves(ref)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        err = max(err, float(np.max(np.abs(a - b))
                             / max(float(np.max(np.abs(b))), 1e-30)))
    return err


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def phase_device() -> dict:
    from importlib import metadata

    import jax

    def version(pkg):
        try:
            return metadata.version(pkg)
        except metadata.PackageNotFoundError:
            return "not installed"

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']} jax={jax.__version__} "
          f"jaxlib={version('jaxlib')} libtpu={version('libtpu')}",
          flush=True)
    return device


def phase_kernel() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.flexai import FlexAIConfig
    from repro.core.flexai.dqn import (_adam_init, dqn_td_grads,
                                       dqn_td_update, init_qnet)
    from repro.core.hmai import HMAIPlatform
    from repro.kernels.conv_dataflow import DATAFLOWS, conv2d
    from repro.kernels.dqn_update import (dqn_td_grads_fused,
                                          dqn_td_update_fused)
    from repro.kernels.protocol import status
    from repro.models.perception.nets import YOLO_WIDTH

    mode = status()["mode"]
    _check(mode == "compiled", f"kernels compiled, not {mode}")
    n = HMAIPlatform().n
    cfg = FlexAIConfig()
    sd, bsz = 3 + 5 * n, cfg.batch_size
    ks = jax.random.split(jax.random.PRNGKey(11), 8)
    eval_p = init_qnet(ks[0], sd, n)
    targ_p = init_qnet(ks[1], sd, n)
    batch = {"s": jax.random.normal(ks[2], (bsz, sd)),
             "a": jax.random.randint(ks[3], (bsz,), 0, n),
             "r": jax.random.normal(ks[4], (bsz,)),
             "s_next": jax.random.normal(ks[5], (bsz, sd)),
             "done": (jax.random.uniform(ks[6], (bsz,)) < 0.1)
             .astype(jnp.float32)}
    opt = _adam_init(eval_p)
    with jax.default_matmul_precision(PRECISION):
        pairs = {
            "td_grads": (
                jax.jit(dqn_td_grads_fused)(eval_p, targ_p, batch),
                jax.jit(dqn_td_grads)(eval_p, targ_p, batch)),
            "td_update": (
                jax.jit(dqn_td_update_fused)(eval_p, targ_p, opt, batch),
                jax.jit(dqn_td_update)(eval_p, targ_p, opt, batch)),
        }
        # a YOLO DarkNet stage-512 3x3 conv on a 416-pixel input (26x26):
        # SconvOD takes 2 sequential Cin steps, MconvMC 4 x 2 channel-pair
        # tiles, SconvIC 4 output-row bands
        cin, cout = int(256 * YOLO_WIDTH), int(512 * YOLO_WIDTH)
        x = jax.random.normal(ks[7], (1, 26, 26, cin))
        w = jax.random.normal(ks[0], (3, 3, cin, cout)) * 0.05
        ref = conv2d(x, w, dataflow="ref", padding="SAME")
        for df in DATAFLOWS:
            pairs[f"conv_{df}"] = (conv2d(x, w, dataflow=df, padding="SAME"),
                                   ref)
    for name, (out, ref) in pairs.items():
        err = _rel_err(out, ref)
        print(f"kernel {name}: max_rel_err={err:.3e} tol={KERNEL_TOL:.0e} "
              f"precision={PRECISION} mode={mode}", flush=True)
        _check(err <= KERNEL_TOL, f"{name} agrees with its reference")


def phase_train(rate_scale: float, weights: pathlib.Path) -> None:
    import numpy as np

    from repro.core.flexai.dqn import HIDDEN, load_dqn_npz
    from repro.launch import train

    argv = ["--flexai", "--td-kernel", "--area", "UB",
            "--rate-scale", str(rate_scale), "--routes", "1",
            "--episodes", "1", "--weights", str(weights)]
    if weights.exists():
        weights.unlink()    # train from the seed, not from an earlier run
    t0 = time.perf_counter()
    _check(train.main(argv) == 0, "launch.train exits 0")
    wall = time.perf_counter() - t0
    params = load_dqn_npz(str(weights))
    _check(params.w1.shape[1] == HIDDEN[0] and params.w2.shape[1] == HIDDEN[1],
           f"EvalNet hidden widths {HIDDEN}")
    _check(all(np.isfinite(np.asarray(p)).all() for p in params),
           "trained weights are finite")
    print(f"train: rate_scale={rate_scale} wall_s={wall:.3f} "
          f"weights={weights.relative_to(ROOT)}", flush=True)


def serve_argv(rate_scale: float, routes: int,
               weights: pathlib.Path | None) -> list:
    argv = ["--placement", "--qos", "edf", "--rate-scale", str(rate_scale),
            "--routes", str(routes)]
    if weights is not None:
        argv += ["--weights", str(weights)]
    return argv


def run_serving(argv: list):
    from repro.launch import serve
    t0 = time.perf_counter()
    eng = serve.run_qos_placement_serving(serve.parse_args(argv))
    _check(eng is not None, "placement serving flags accepted")
    return eng, time.perf_counter() - t0


def phase_serve(argv: list):
    eng, wall = run_serving(argv)
    s = eng.stats()
    print(f"serve: served {s['completed']}/{s['submitted']} "
          f"shed {s['shed']} dispatches {s['dispatches']} "
          f"waves {s['waves']} wall_s {wall:.3f} "
          f"miss_rate {s['miss_rate']:.4f} "
          f"mean_stm {s['mean_stm_rate']:.4f}", flush=True)
    _check(s["resolved"] == s["submitted"]
           and s["in_flight"] == 0 and s["queued"] == 0,
           "every submitted route resolved, served or shed")
    _check(s["completed"] >= 1, "at least one route served")
    return eng


def replay_f32(spec, q, placements, recs64) -> tuple:
    """Replay ``platform_step``'s clock arithmetic for route ``q`` in NumPy
    float32, operation for operation, next to the float64
    ``HMAIPlatform`` records ``recs64`` of the same placements.  Returns
    ``(met, outside)``: the float32 deadline verdicts met, and the number
    of tasks whose float32 response strays from the float64 one by more
    than float32 rounding accounts for — a running bound of half an ulp
    per rounded operation plus each input's own conversion error, carried
    per core along its chain of ``avail`` clocks."""
    import numpy as np

    from repro.core.tasks import tasks_to_arrays
    f32 = np.float32
    ta = tasks_to_arrays(q)
    exec_time = np.asarray(spec.exec_time, f32)
    avail = np.zeros(exec_time.shape[0], f32)
    err = np.zeros(exec_time.shape[0])        # bound on |avail32 - avail64|
    met = outside = 0
    for i, (a, rec) in enumerate(zip(placements, recs64)):
        arr, et = ta.arrival[i], exec_time[a, ta.kind[i]]
        e_arr = abs(float(arr) - rec.task.arrival_time)
        finish = f32(max(arr, avail[a]) + et)
        response = f32(finish - arr)
        err[a] = (max(e_arr, err[a]) + abs(float(et) - rec.exec_time)
                  + float(np.spacing(finish)) / 2)
        avail[a] = finish
        bound = err[a] + e_arr + float(np.spacing(abs(response))) / 2
        met += bool(response <= ta.safety[i])
        outside += abs(float(response) - rec.response_time) > bound
    return met, outside


def phase_correctness(eng, argv: list) -> None:
    import jax
    import numpy as np

    from repro.core.environment import build_task_queue
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import platform_init, stack_states
    from repro.core.schedulers.scan import scan_schedule
    from repro.core.tasks import stack_task_arrays
    from repro.launch import serve
    from repro.serve.qos import _segment_fn

    args = serve.parse_args(argv)
    queues = {}
    for req in sorted(eng.completed, key=lambda r: r.uid):
        q = build_task_queue(serve._route_params(args, req.uid))
        _check(len(q) == req.n_tasks, f"route {req.uid} rebuilt")
        queues[req.uid] = q
        placements = req.summary["placements"]
        plat = HMAIPlatform(capacity_scale=args.rate_scale)
        for task, a in zip(q, placements):
            plat.execute(task, int(a))
        ref = plat.summary()
        met32, outside = replay_f32(eng.spec, q, placements, plat.records)
        got = req.summary
        met_got = round(got["stm_rate"] * got["tasks"])
        met_ref = round(ref["stm_rate"] * ref["tasks"])
        print(f"replay route {req.uid}: tasks {got['tasks']} met engine "
              f"{met_got} f32 {met32} f64 {met_ref} | stm engine "
              f"{got['stm_rate']:.6f} f64 {ref['stm_rate']:.6f} "
              f"|d|={abs(ref['stm_rate'] - got['stm_rate']):.2e} | "
              f"responses outside the f32 rounding bound {outside}",
              flush=True)
        _check(got["tasks"] == ref["tasks"] == len(q),
               f"route {req.uid} replays every task")
        _check(met_got == met32,
               f"route {req.uid} verdicts equal the float32 replay")
        _check(outside == 0,
               f"route {req.uid} within float32 rounding of float64")

    # one FlexAI route, the same jitted segment, on the CPU backend
    cpu = jax.devices("cpu")[0]
    req = min(eng.completed, key=lambda r: r.uid)
    seg = _segment_fn(eng.spec, eng.backlog_scale)
    on_cpu = jax.device_put(
        (eng.params, stack_task_arrays([req.tasks]),
         stack_states([platform_init(eng.spec.n)])), cpu)
    _, recs = seg(*on_cpu)
    acts = np.asarray(recs.action)[0, : req.n_tasks]
    share = float(np.mean(acts == req.summary["placements"]))
    print(f"cpu segment route {req.uid}: placements agreeing with the chip "
          f"{share:.6f} (Q-net matmul precision: highest)", flush=True)

    # a matmul-free route must be bit-equal on chip and CPU
    q = queues[req.uid]
    plat = HMAIPlatform(capacity_scale=args.rate_scale)
    chip = scan_schedule("ata", plat, q)
    with jax.default_device(cpu):
        host = scan_schedule("ata", plat, q)
    equal = bool(np.array_equal(chip["placements"], host["placements"]))
    print(f"ata scan_schedule route {req.uid}: placements chip==cpu "
          f"{equal} stm chip {chip['stm_rate']:.6f} "
          f"cpu {host['stm_rate']:.6f}", flush=True)
    _check(equal, "ATA placements bit-equal on chip and CPU")


def phase_mesh(rate_scale: float, routes: int, chips: int) -> None:
    import jax
    import numpy as np

    from repro.compat import make_mesh
    from repro.core.environment import EnvironmentParams, build_task_queue
    from repro.core.flexai import FlexAIConfig, dp_train_init, make_dp_train_fn
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import stack_task_arrays, tasks_to_arrays
    from repro.serve.durability import digests_equal, serving_digest

    argv = serve_argv(rate_scale, routes, None)
    single, t1 = run_serving(argv)
    sharded, t4 = run_serving(argv + ["--shard"])
    _check(sharded.mesh is not None and sharded.mesh.size == chips,
           f"waves sharded over {chips} chips")
    d1, d4 = serving_digest(single), serving_digest(sharded)
    same = digests_equal(d1, d4)
    s1, s4 = single.stats(), sharded.stats()
    print(f"mesh serve: routes {routes} rate_scale {rate_scale} "
          f"slots {sharded.cfg.slots} "
          f"served {s4['completed']}/{s4['submitted']} shed {s4['shed']} "
          f"dispatches {s4['dispatches']} wall_s 1-chip {t1:.3f} "
          f"{chips}-chip {t4:.3f} placements+digest equal {same}",
          flush=True)
    _check(s1["completed"] == s4["completed"] >= 1, "routes served")
    _check(same, "sharded serving_digest equals the single-device one")

    # a few data-parallel trainer steps, sharded against unsharded, two
    # lanes per chip
    lanes, steps, rs = 2 * chips, 64, 0.05
    plat = HMAIPlatform(capacity_scale=rs)
    spec = spec_from_platform(plat)
    cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=2,
                       eps_decay_steps=500, replay_capacity=2048, seed=2)
    batch = stack_task_arrays([tasks_to_arrays(build_task_queue(
        EnvironmentParams(route_km=0.02, rate_scale=rs, seed=70 + i,
                          max_times_turn=2, max_times_reverse=1,
                          max_duration_turn=4.0, max_duration_reverse=6.0)
    )[:steps]) for i in range(lanes)])
    ts0 = dp_train_init(jax.random.PRNGKey(cfg.seed), 3 + 5 * plat.n,
                        plat.n, cfg.replay_capacity, lanes)
    ref = jax.device_get(make_dp_train_fn(spec, cfg, lanes)(ts0, batch))
    mesh = make_mesh((chips,), ("routes",))
    out = jax.device_get(
        make_dp_train_fn(spec, cfg, lanes, mesh=mesh)(ts0, batch))
    acts = bool(np.array_equal(out[2].action, ref[2].action))
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
              for a, b in zip(out[0].eval_p, ref[0].eval_p))
    print(f"mesh dp: lanes {lanes} chips {chips} "
          f"steps {batch.arrival.shape[1]} "
          f"updates {int(out[0].updates)} actions equal {acts} "
          f"max |param diff| {err:.3e} tol={DP_PARAM_TOL:.0e}", flush=True)
    _check(acts and int(out[0].updates) == int(ref[0].updates)
           and err <= DP_PARAM_TOL, "sharded DP trainer tracks unsharded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path")
    args = ap.parse_args(argv)

    # the correctness phase reruns a route on the in-process CPU backend
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    from repro.compat import enable_compile_cache

    cache = enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: the first JAX device is {platform!r}, not a "
              f"TPU; nothing was run", file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(jax.devices())}", file=sys.stderr)
        return 1
    print(f"compile cache: {cache}", flush=True)
    device = phase_device()
    if args.chips > 1:
        phase_mesh(MESH_RATE_SCALE, SERVE_ROUTES, args.chips)
    else:
        phase_kernel()
        phase_train(RATE_SCALE, WEIGHTS)
        argv = serve_argv(RATE_SCALE, SERVE_ROUTES, WEIGHTS)
        eng = phase_serve(argv)
        phase_correctness(eng, argv)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
