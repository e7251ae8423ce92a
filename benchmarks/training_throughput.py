"""FlexAI training throughput: Python-loop vs fused scan vs data-parallel.

Three trainers over identical routes and hyperparameters:

* **loop** — ``FlexAIAgent.train``: one Python iteration (plus 1-2 jit
  dispatches) per task, the seed implementation;
* **fused** — ``ScanFlexAI`` single lane: the whole episode (act, platform
  step, reward, replay write, TD update) in one ``lax.scan`` dispatch;
* **dp** — ``make_dp_train_fn``: one synchronized agent over a route
  batch, per-step gradient all-reduce, sharded over forced host devices
  (subprocess children, since ``--xla_force_host_platform_device_count``
  must be set before jax imports).  Each multi-device child re-times the
  *unsharded* DP runner on the same global batch in the same process, so
  the scaling factor compares like with like, and asserts loss/parameter
  parity between the two before reporting.

A separate equal-episode quality run (eval-based model selection on both
paths, averaged over seeds) records final held-out-queue STM so the
fused path's placement quality is auditable against the loop trainer's.

Honesty note: on this CPU host both trainers share the TD-update matmul
compute (~0.5 ms/update), so the full-trainer ratio cannot approach the
~29x inference-only ratio — the ``acting_*`` rows isolate the per-task
host overhead the fused engine does remove.  On accelerator hardware the
update compute shrinks and the ratio becomes dispatch-bound again.

Emits the standard benchmark rows plus ``BENCH_training.json`` (repo
root) with the speedup and parity columns.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

DP_DEVICE_COUNTS = (1, 4)
RESULT_TAG = "TRAINING_RESULT "


def _cfg(seed: int = 7, **over):
    from repro.core.flexai import FlexAIConfig
    kw = dict(lr=1e-3, gamma=0.98, batch_size=32, min_replay=128,
              update_every=2, eps_decay_steps=2000, target_sync_every=200,
              replay_capacity=8192, seed=seed)
    kw.update(over)
    return FlexAIConfig(**kw)


def _dp_cfg():
    """DP config: per-lane batches kept small (the global batch is
    lanes x batch_size) so the unsharded baseline is dispatch-bound
    rather than intra-op-threaded — the regime route sharding targets."""
    from repro.core.flexai import FlexAIConfig
    return FlexAIConfig(lr=1e-3, gamma=0.98, batch_size=32, min_replay=128,
                        update_every=1, eps_decay_steps=2000,
                        target_sync_every=200, replay_capacity=1024, seed=7)


def _routes(n: int, tasks: int, seed0: int = 70):
    """n unique routes trimmed to exactly ``tasks`` tasks each (Task lists
    for the loop trainer; callers convert to TaskArrays for the engines)."""
    from benchmarks.common import queues_for
    return [q[:tasks] for q in queues_for("UB", n, km=0.05, seed0=seed0)]


# ---------------------------------------------------------------------------
# loop vs fused (in-process, single device)
# ---------------------------------------------------------------------------

def _time_pair(cfg, queues, episodes: int, reps: int = 3
               ) -> tuple[float, float]:
    """(loop_s, fused_s) for ``episodes`` from-scratch episodes at equal
    config.  Compiles are warmed out of band (a throwaway learner warms
    the module-level ``dqn_update``; each timing agent's per-instance
    ``q_values`` jit warms on a dummy state, which mutates nothing); the
    fused side times the raw engine fn — wrapper summaries are host-side
    reporting, not training.  The two variants alternate for ``reps``
    fresh-state repetitions and each keeps its best window (the
    container's CPU budget swings at the multi-second scale)."""
    import jax
    import numpy as np

    from benchmarks.common import platform
    from repro.core.flexai import FlexAIAgent
    from repro.core.flexai.engine import make_train_fn, train_init
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import tasks_to_arrays

    plat = platform()
    spec = spec_from_platform(plat)
    state_dim = 3 + 5 * plat.n

    if cfg.min_replay < 10**9:
        warm = FlexAIAgent(platform(), cfg)
        warm.learner.update({
            "s": np.zeros((cfg.batch_size, state_dim), np.float32),
            "a": np.zeros(cfg.batch_size, np.int32),
            "r": np.zeros(cfg.batch_size, np.float32),
            "s_next": np.zeros((cfg.batch_size, state_dim), np.float32),
            "done": np.zeros(cfg.batch_size, np.float32)})
    routes = [tasks_to_arrays(q) for q in queues]
    fn = make_train_fn(spec, cfg)
    key = jax.random.PRNGKey(cfg.seed)
    warm_ts = train_init(key, state_dim, plat.n, cfg.replay_capacity)
    jax.block_until_ready(fn(warm_ts, routes[0])[0].eval_p)

    t_loop, t_fused = float("inf"), float("inf")
    for _ in range(reps):
        agent = FlexAIAgent(platform(), cfg)
        agent.learner.q_values(np.zeros((1, state_dim), np.float32))
        p = platform()
        t0 = time.perf_counter()
        agent.train(p, queues, episodes=episodes)
        t_loop = min(t_loop, time.perf_counter() - t0)

        ts = train_init(key, state_dim, plat.n, cfg.replay_capacity)
        t0 = time.perf_counter()
        for ep in range(episodes):
            ts = fn(ts, routes[ep % len(routes)])[0]
        jax.block_until_ready(ts.eval_p)
        t_fused = min(t_fused, time.perf_counter() - t0)
    return t_loop, t_fused


def _loop_vs_fused(tasks: int, episodes: int, quality_episodes: int,
                   quality_seeds) -> dict:
    import numpy as np

    from benchmarks.common import platform
    from repro.core.flexai import FlexAIAgent, ScanFlexAI

    queues = _routes(3, tasks)
    val_q = _routes(1, tasks, seed0=90)[0]
    steps = tasks * episodes

    # -- timing at equal episodes and equal config.  Two cadences:
    # the full trainer (TD update every update_every steps — both paths
    # share the ~0.5 ms TD-update matmul compute, which floors the
    # achievable ratio on a CPU host), and the acting path alone
    # (min_replay never reached), which isolates the per-task host
    # overhead the fused engine actually eliminates.
    t_loop, t_fused = _time_pair(_cfg(), queues, episodes)
    t_loop_act, t_fused_act = _time_pair(
        _cfg(min_replay=10**9), queues, episodes)

    # -- quality at equal episodes: eval-based model selection on both
    # paths, averaged over seeds (single-seed DQN outcomes swing by
    # +-0.1 STM on these short runs)
    def tail_loss(losses):
        tail = np.asarray(losses[-max(len(losses) // 4, 1):], np.float64)
        return float(tail.mean()) if len(tail) else np.nan

    loop_stms, fused_stms = [], []
    loop_tails, fused_tails = [], []
    for seed in quality_seeds:
        cfg_q = _cfg(seed=seed)
        plat_q = platform()
        loop_q = FlexAIAgent(plat_q, cfg_q)
        loop_q.train(plat_q, queues, episodes=quality_episodes,
                     eval_queue=val_q, eval_every=2)
        loop_stms.append(loop_q.schedule_scan(platform(),
                                              val_q)["stm_rate"])
        loop_tails.append(tail_loss(loop_q.losses))
        fused_q = ScanFlexAI(platform(), cfg_q)
        fused_q.train(queues, episodes=quality_episodes,
                      eval_queue=val_q, eval_every=2)
        fused_stms.append(fused_q.schedule(val_q)["stm_rate"])
        fused_tails.append(tail_loss(fused_q.losses))
    loop_stm = float(np.mean(loop_stms))
    fused_stm = float(np.mean(fused_stms))

    return {
        "tasks_per_route": tasks,
        "episodes": episodes,
        "loop": {"train_s": round(t_loop, 3),
                 "env_steps_per_s": round(steps / t_loop, 1),
                 "acting_env_steps_per_s": round(steps / t_loop_act, 1),
                 "eval_stm_mean": round(loop_stm, 4),
                 "eval_stm_by_seed": [round(s, 4) for s in loop_stms],
                 "tail_mean_loss": float(np.nanmean(loop_tails))},
        "fused": {"train_s": round(t_fused, 3),
                  "env_steps_per_s": round(steps / t_fused, 1),
                  "acting_env_steps_per_s": round(steps / t_fused_act, 1),
                  "eval_stm_mean": round(fused_stm, 4),
                  "eval_stm_by_seed": [round(s, 4) for s in fused_stms],
                  "tail_mean_loss": float(np.nanmean(fused_tails))},
        "fused_speedup_vs_loop": round(t_loop / t_fused, 2),
        "acting_speedup_vs_loop": round(t_loop_act / t_fused_act, 2),
        "note": "both trainers share the TD-update matmul compute "
                "(~0.5 ms/update on this CPU host), which bounds the "
                "full-trainer ratio; the acting-path ratio shows the "
                "per-task host overhead the fused engine removes "
                "(cf. the ~29x inference-only ratio in BENCH_scheduler)",
        # model selection keeps the best-eval weights on both paths, so
        # "no worse" is checked on the seed mean with a small tolerance
        "eval_parity_ok": bool(fused_stm >= loop_stm - 0.02),
    }


# ---------------------------------------------------------------------------
# fused TD-update kernel arm (report-only on CPU hosts)
# ---------------------------------------------------------------------------

def _td_kernel_arm(tasks: int, episodes: int, reps: int = 3) -> dict:
    """Times the fused engine with ``td_kernel=True`` against the default
    XLA TD update on identical routes/config, and checks loss parity.

    On a CPU host the kernel runs in interpret mode (the Pallas body
    lowered to plain XLA ops), so the ratio here is NOT a hardware kernel
    claim in either direction — it is reported, never gated.  The
    compiled ratio lives in ``BENCH_kernels.json``'s compiled leg, which
    runs only off the CPU backend."""
    import jax
    import numpy as np

    from benchmarks.common import platform
    from repro.core.flexai.engine import make_train_fn, train_init
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import tasks_to_arrays

    cfg = _cfg()
    plat = platform()
    spec = spec_from_platform(plat)
    state_dim = 3 + 5 * plat.n
    routes = [tasks_to_arrays(q) for q in _routes(3, tasks)]
    key = jax.random.PRNGKey(cfg.seed)

    def episode_time(fn):
        ts0 = train_init(key, state_dim, plat.n, cfg.replay_capacity)
        jax.block_until_ready(fn(ts0, routes[0])[0].eval_p)   # warm compile
        best = float("inf")
        last = None
        for _ in range(reps):
            ts = train_init(key, state_dim, plat.n, cfg.replay_capacity)
            t0 = time.perf_counter()
            for ep in range(episodes):
                ts = fn(ts, routes[ep % len(routes)])[0]
            jax.block_until_ready(ts.eval_p)
            best = min(best, time.perf_counter() - t0)
            last = ts
        return best, last

    t_off, ts_off = episode_time(make_train_fn(spec, cfg))
    t_on, ts_on = episode_time(make_train_fn(spec, cfg, td_kernel=True))
    max_p = max(float(jnp_abs_max(a, b))
                for a, b in zip(ts_off.eval_p, ts_on.eval_p))
    steps = tasks * episodes
    return {
        "env_steps_per_s_off": round(steps / t_off, 1),
        "env_steps_per_s_on": round(steps / t_on, 1),
        "on_vs_off_ratio": round(t_off / t_on, 3),
        "final_param_max_diff": max_p,
        "parity_ok": bool(max_p <= 1e-5),
        "mode": "interpret (CPU host)" if _interpret_mode()
                else "compiled",
        "note": "interpret-mode Pallas on a CPU host executes the kernel "
                "body as plain XLA ops — this ratio says nothing about "
                "hardware kernel speed; see BENCH_kernels.json compiled "
                "leg for the honest accelerator number (reported, not "
                "gated)",
    }


def jnp_abs_max(a, b):
    import jax.numpy as jnp
    return jnp.max(jnp.abs(a - b))


def _interpret_mode() -> bool:
    from repro.compat import pallas_interpret_default
    return pallas_interpret_default()


# ---------------------------------------------------------------------------
# data-parallel child (forced host devices)
# ---------------------------------------------------------------------------

def _child_main(args) -> None:
    import jax
    import numpy as np

    from benchmarks.common import arm_devices, platform
    from repro.compat import make_mesh
    from repro.core.flexai import dp_train_init, make_dp_train_fn
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import stack_task_arrays, tasks_to_arrays

    n_dev = arm_devices(args.devices)
    cfg = _dp_cfg()
    plat = platform()
    spec = spec_from_platform(plat)
    lanes = args.dp_lanes
    uniq = _routes(min(lanes, 8), args.tasks)
    batch = stack_task_arrays(
        [tasks_to_arrays(uniq[i % len(uniq)]) for i in range(lanes)])
    state_dim = 3 + 5 * plat.n
    key = jax.random.PRNGKey(cfg.seed)
    ts0 = dp_train_init(key, state_dim, plat.n, cfg.replay_capacity, lanes)
    steps = int(np.asarray(batch.valid).sum())

    def best_of(fn, iters):
        result = fn()  # warmup / compile
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            result = fn()
            best = min(best, time.perf_counter() - t0)
        return result, best

    fn_u = make_dp_train_fn(spec, cfg, lanes)
    result = {
        "devices": n_dev,
        "lanes": lanes,
        "tasks_per_lane": args.tasks,
    }
    if n_dev == 1:
        _, t_u = best_of(
            lambda: jax.block_until_ready(fn_u(ts0, batch)), args.iters)
        result["unsharded_env_steps_per_s"] = round(steps / t_u, 1)
    else:
        from repro.core.flexai import FlexAIConfig

        mesh = make_mesh((n_dev,), ("routes",))
        fn_s = make_dp_train_fn(spec, cfg, lanes, mesh=mesh)
        jax.block_until_ready(fn_u(ts0, batch))  # compile warmups
        jax.block_until_ready(fn_s(ts0, batch))
        # interleaved best-of timing: the container's CPU budget swings
        # at the multi-second scale, so unsharded/sharded runs alternate
        # and each variant keeps its best window (the sharded_engine
        # convention for this noisy host)
        t_u, t_s = float("inf"), float("inf")
        for _ in range(max(args.iters, 3)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn_u(ts0, batch))
            t_u = min(t_u, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fn_s(ts0, batch))
            t_s = min(t_s, time.perf_counter() - t0)
        result["unsharded_env_steps_per_s"] = round(steps / t_u, 1)

        # Parity runs on a dedicated short-route / fast-epsilon-decay
        # segment: over long routes the policy feedback loop amplifies
        # ulp-level fp differences (pmean reduction order vs the local
        # lane mean) into diverged action trajectories, so trajectory
        # equality is only a meaningful contract before that drift can
        # compound.  Same init + same batch -> identical placements,
        # params/losses to accumulated-fp32 tolerance.
        p_cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=2,
                             eps_decay_steps=500, replay_capacity=2048,
                             seed=7)
        p_uniq = _routes(min(lanes, 8), 128)
        p_batch = stack_task_arrays(
            [tasks_to_arrays(p_uniq[i % len(p_uniq)]) for i in range(lanes)])
        p_ts = dp_train_init(key, state_dim, plat.n, p_cfg.replay_capacity,
                             lanes)
        p_u = jax.block_until_ready(
            make_dp_train_fn(spec, p_cfg, lanes)(p_ts, p_batch))
        p_s = jax.block_until_ready(
            make_dp_train_fn(spec, p_cfg, lanes, mesh=mesh)(p_ts, p_batch))
        rel = 0.0
        for a, b in zip(p_u[0].eval_p, p_s[0].eval_p):
            a, b = np.asarray(a), np.asarray(b)
            rel = max(rel, float(np.max(np.abs(a - b))
                                 / max(np.max(np.abs(a)), 1e-9)))
        loss_diff = float(np.max(np.abs(np.asarray(p_u[3])
                                        - np.asarray(p_s[3]))))
        placements_equal = bool(np.array_equal(
            np.asarray(p_u[2].action), np.asarray(p_s[2].action)))
        assert placements_equal, \
            "sharded DP action trajectory diverges from unsharded"
        assert rel < 5e-3 and loss_diff < 1e-3, \
            f"sharded/unsharded DP divergence: params {rel} loss {loss_diff}"
        assert int(p_u[0].env_steps) == int(p_s[0].env_steps)
        result.update({
            "sharded_env_steps_per_s": round(steps / t_s, 1),
            "sharded_speedup_vs_unsharded": round(t_u / t_s, 2),
            "parity_placements_equal": placements_equal,
            "parity_params_rel_diff": rel,
            "parity_loss_max_diff": loss_diff,
            "parity_ok": True,
        })
    print(RESULT_TAG + json.dumps(result))


def _spawn(devices: int, lanes: int, tasks: int, iters: int) -> dict:
    from benchmarks.common import run_device_arm
    return run_device_arm(
        "training_throughput", devices,
        ["--dp-lanes", lanes, "--tasks", tasks, "--iters", iters],
        RESULT_TAG)


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(quick: bool = True) -> list:
    from benchmarks.common import host_tuning, row, save

    tasks = 384 if quick else 1024
    episodes = 2 if quick else 4
    quality_episodes = 8 if quick else 16
    quality_seeds = (7, 8, 9) if quick else (7, 8, 9, 10, 11)
    dp_lanes = 64
    dp_tasks = 192 if quick else 384

    base = _loop_vs_fused(tasks, episodes, quality_episodes, quality_seeds)
    tdk = _td_kernel_arm(tasks, episodes)
    dp = {d: _spawn(d, dp_lanes, dp_tasks, iters=3 if quick else 5)
          for d in DP_DEVICE_COUNTS}
    # headline scaling is the 4-device child's paired in-process ratio
    # (cross-child comparisons see different machine-noise windows)
    dp_speedup = dp[4]["sharded_speedup_vs_unsharded"]

    summary = dict(base)
    summary["td_kernel"] = tdk
    summary["dp"] = {
        "lanes": dp_lanes,
        "tasks_per_lane": dp_tasks,
        "by_device_count": dp,
        "speedup_4dev_vs_1dev": dp_speedup,
        "parity_ok": bool(dp[4].get("parity_ok", False)),
        "note": "this container exposes 2 physical cores, so 4 forced "
                "host devices oversubscribe 2:1; scaling saturates near "
                "the measured ratio and clears 1.5x only on hosts with "
                ">= 4 cores (collective cost is negligible: an "
                "axis-free shard_map variant times the same)",
    }
    summary["host_tuning"] = host_tuning(devices=4)
    with open(os.path.join(os.getcwd(), "BENCH_training.json"), "w") as f:
        json.dump(summary, f, indent=1)

    rows = [
        row("training/loop_env_steps_per_s", 0.0,
            base["loop"]["env_steps_per_s"]),
        row("training/fused_env_steps_per_s", 0.0,
            base["fused"]["env_steps_per_s"]),
        row("training/fused_speedup_vs_loop", 0.0,
            f"{base['fused_speedup_vs_loop']}x"),
        row("training/acting_speedup_vs_loop", 0.0,
            f"{base['acting_speedup_vs_loop']}x"),
        row("training/eval_parity_ok", 0.0, base["eval_parity_ok"],
            loop_stm=base["loop"]["eval_stm_mean"],
            fused_stm=base["fused"]["eval_stm_mean"]),
        row("training/dp_1dev_env_steps_per_s", 0.0,
            dp[1]["unsharded_env_steps_per_s"]),
        row("training/dp_4dev_env_steps_per_s", 0.0,
            dp[4]["sharded_env_steps_per_s"]),
        row("training/dp_speedup_4dev_vs_1dev", 0.0, f"{dp_speedup}x"),
        row("training/dp_parity_ok", 0.0,
            summary["dp"]["parity_ok"]),
        row("training/td_kernel_env_steps_per_s", 0.0,
            tdk["env_steps_per_s_on"], mode=tdk["mode"]),
        row("training/td_kernel_on_vs_off_ratio", 0.0,
            f"{tdk['on_vs_off_ratio']}x", mode=tdk["mode"]),
        row("training/td_kernel_parity_ok", 0.0, tdk["parity_ok"]),
    ]
    save("training_throughput", rows)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--dp-lanes", type=int, default=4)
    ap.add_argument("--tasks", type=int, default=256)
    ap.add_argument("--iters", type=int, default=2)
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
