"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m --smoke \
        --steps 100 --ckpt-dir /tmp/ckpt

Runs the fault-tolerant training driver (checkpoint every N steps, SIGTERM
preemption handling, deterministic restart).  On a real pod the same entry
point runs per host with jax.distributed initialization; on this container
it exercises the identical code path on the local device.

With ``--flexai`` the launcher instead trains the FlexAI scheduling agent
on the device-resident fused engine (the "long offline run" producing the
benchmark checkpoints) — data-parallel over all visible devices with
``--dp --shard``:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.train --flexai --area UB \
        --episodes 100 --dp --dp-lanes 4 --shard \
        --weights experiments/flexai/agent_ub.npz

``--td-kernel`` swaps the TD update inside the training scan for the
fused Pallas kernel (``repro.kernels.dqn_update``): EvalNet forward,
double-DQN target, Huber loss, hand-derived backward, global-norm clip
and Adam in one VMEM-resident pass.  On the CPU backend it runs in
interpret mode (numerics-faithful, not a speed claim); on a TPU it runs
the compiled Mosaic kernel (see ``repro.kernels.protocol``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np

from repro.compat import enable_compile_cache
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models.api import model_api
from repro.sharding import unbox
from repro.train import checkpoint as ckpt_lib
from repro.train.data import DataConfig, batch_fn
from repro.train.fault_tolerance import (PreemptionGuard, elastic_restore,
                                         run_with_fault_tolerance)
from repro.train.loop import TrainHyper, init_train_state, make_train_step


def _trainer_snapshot(trainer, episode: int) -> dict:
    """Checkpoint pytree for a ``ScanFlexAI``: the full ``TrainState``
    (EvalNet/TargNet/Adam/replay/counters/key — every dtype the manifest
    path must round-trip), the episode cursor, and the model-selection
    best-so-far, so an interrupted run resumes bit-exactly."""
    has_best = trainer._best_params is not None
    return {
        "ts": trainer.ts,
        "episode": np.int32(episode),
        "best_stm": np.float64(trainer._best_stm),
        "has_best": np.bool_(has_best),
        "best_p": (trainer._best_params if has_best
                   else trainer.eval_params()),
    }


def build_flexai_trainer(*, seed: int = 0, lr: float = 1e-3,
                         rate_scale: float = 1.0, lanes: int = 1,
                         mesh=None, dp: bool = False,
                         td_kernel: bool = False):
    """The ``ScanFlexAI`` trainer as ``--flexai`` trains it: the HMAI
    platform at ``rate_scale`` of its Table-8 capacity (the camera-rate
    factor of the routes), Q-net weights from ``seed``, and the
    launcher's ``FlexAIConfig`` (gamma 0.98, replay warm-up 256, a TD
    update every second step, epsilon decay over 40,000 steps, TargNet
    sync every 500 updates)."""
    from repro.core.flexai import FlexAIConfig, ScanFlexAI
    from repro.core.hmai import HMAIPlatform

    cfg = FlexAIConfig(lr=lr, gamma=0.98, min_replay=256, update_every=2,
                       eps_decay_steps=40_000, target_sync_every=500,
                       seed=seed)
    plat = HMAIPlatform(capacity_scale=rate_scale)
    return ScanFlexAI(plat, cfg, lanes=lanes, mesh=mesh, dp=dp,
                      td_kernel=td_kernel)


def run_flexai_training(args) -> int:
    """Device-resident FlexAI training: fused episodes, optional
    data-parallel sharding, eval-based model selection, npz checkpoint
    (+ loss-history sidecar) shared with ``FlexAIAgent``."""
    from repro.compat import make_mesh
    from repro.core.environment import (Area, EnvironmentParams,
                                        build_task_queue)

    mesh = None
    if args.shard:
        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("routes",))
        print(f"training mesh: {n_dev} device(s) on axis 'routes'")
    lanes = args.dp_lanes if args.dp else 1
    trainer = build_flexai_trainer(
        seed=args.seed, lr=args.lr, rate_scale=args.rate_scale, lanes=lanes,
        mesh=mesh, dp=args.dp, td_kernel=args.td_kernel)
    if args.td_kernel:
        from repro.compat import pallas_interpret_default
        mode = ("interpret (CPU backend — plain XLA ops, not a speed claim)"
                if pallas_interpret_default() else "compiled")
        print(f"TD update: fused Pallas kernel, {mode}")
    if args.weights and os.path.exists(args.weights):
        trainer.load_weights(args.weights)
        print(f"resumed weights from {args.weights}")

    # full-state snapshots (TrainState + episode + model-selection best):
    # unlike --weights, a resume from these is bit-exact — the replay
    # ring, PRNG key and counters all ride along
    saver = None
    start_ep = 0
    if args.snapshot_dir:
        saver = ckpt_lib.AsyncCheckpointer(args.snapshot_dir)
        if args.resume:
            path = ckpt_lib.latest_checkpoint(args.snapshot_dir)
            if path is not None:
                snap = ckpt_lib.restore_checkpoint(
                    path, _trainer_snapshot(trainer, 0))
                trainer.ts = snap["ts"]
                # scalars come from the raw manifest arrays: device_put
                # under disabled x64 would round the float64 best-stm
                # through float32 and could flip a later model-selection
                # comparison
                _, raw, names = ckpt_lib.load_checkpoint_arrays(path)
                host = dict(zip(names, raw))
                start_ep = int(host["['episode']"])
                if bool(host["['has_best']"]):
                    trainer._best_stm = float(host["['best_stm']"])
                    trainer._best_params = snap["best_p"]
                print(f"resumed trainer snapshot at episode {start_ep}")

    def on_episode(ep, tr):
        if saver is not None and args.snapshot_every > 0 \
                and (ep + 1) % args.snapshot_every == 0:
            saver.save(ep + 1, _trainer_snapshot(tr, ep + 1))

    area = Area(args.area)
    queues = [build_task_queue(EnvironmentParams(
        area=area, route_km=args.route_km,
        rate_scale=args.rate_scale, seed=args.seed + i))
        for i in range(args.routes)]
    val_q = build_task_queue(EnvironmentParams(
        area=area, route_km=args.route_km,
        rate_scale=args.rate_scale, seed=args.seed + 50))
    n_tasks = sum(len(q) for q in queues)
    mode = f"dp lanes={lanes}" if args.dp else "single-lane"
    print(f"flexai {mode}: {args.routes} routes / {n_tasks} tasks, "
          f"{args.episodes} episodes, area={args.area}")

    t0 = time.perf_counter()
    # --episodes counts *new* episodes; the engine's `episodes` is the
    # global end index (range(start_episode, episodes))
    history = trainer.train(queues, episodes=start_ep + args.episodes,
                            eval_queue=val_q, eval_every=args.eval_every,
                            on_episode=on_episode, start_episode=start_ep)
    if saver is not None:
        saver.wait()
    dt = time.perf_counter() - t0
    for ep, h in enumerate(history):
        if "eval_stm" in h:
            print(f"  episode {start_ep + ep + 1}: eval_stm={h['eval_stm']}")
    steps = int(np.asarray(trainer.ts.env_steps).sum())
    print(f"trained {steps} env steps in {dt:.2f}s "
          f"({steps / max(dt, 1e-9):.0f} steps/s), "
          f"best_eval_stm={trainer.best_eval_stm}")
    if args.weights:
        os.makedirs(os.path.dirname(args.weights) or ".", exist_ok=True)
        trainer.save_weights(args.weights)
        np.save(args.weights[: -len(".npz")] + "_losses.npy",
                np.asarray(trainer.losses, np.float64))
        print(f"saved weights to {args.weights}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--flexai", action="store_true",
                    help="train the FlexAI scheduling agent on the fused "
                         "device-resident engine instead of an LLM arch")
    ap.add_argument("--area", default="UB",
                    help="[flexai] driving area (UB/UHW/HW)")
    ap.add_argument("--episodes", type=int, default=50)
    ap.add_argument("--routes", type=int, default=4)
    ap.add_argument("--route-km", type=float, default=0.15)
    ap.add_argument("--rate-scale", type=float, default=0.05)
    ap.add_argument("--eval-every", type=int, default=5)
    ap.add_argument("--dp", action="store_true",
                    help="[flexai] data-parallel trainer (one synchronized "
                         "agent over a route batch)")
    ap.add_argument("--dp-lanes", type=int, default=4)
    ap.add_argument("--td-kernel", action="store_true",
                    help="use the fused Pallas TD-update kernel "
                         "(kernels/dqn_update) inside the training scan; "
                         "interpret mode on the CPU backend, compiled "
                         "on a TPU")
    ap.add_argument("--shard", action="store_true",
                    help="[flexai] shard lanes over all visible devices")
    ap.add_argument("--weights", default=None,
                    help="[flexai] npz checkpoint to resume from / save to")
    ap.add_argument("--snapshot-dir", default=None,
                    help="[flexai] directory for full-state trainer "
                         "snapshots (TrainState + episode + best)")
    ap.add_argument("--snapshot-every", type=int, default=1,
                    help="[flexai] snapshot cadence in episodes (0=off)")
    ap.add_argument("--resume", action="store_true",
                    help="[flexai] resume bit-exactly from the latest "
                         "snapshot in --snapshot-dir")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8_ef"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.flexai:
        if args.shard and not args.dp:
            ap.error("--shard requires --dp: sharding splits the DP "
                     "route batch (use --dp-lanes for its width)")
        if args.weights and not args.weights.endswith(".npz"):
            # np.savez appends .npz on write; normalize up front so the
            # resume check and the loss-sidecar path see the real file
            args.weights += ".npz"
        return run_flexai_training(args)
    if args.arch is None:
        ap.error("--arch is required (unless --flexai)")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = model_api(cfg)
    hyper = TrainHyper(peak_lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                       total_steps=args.steps, compression=args.compression)
    data = DataConfig(batch_size=args.batch_size, seq_len=args.seq_len)
    bat = batch_fn(cfg, data)
    step = jax.jit(make_train_step(api, hyper))

    params = unbox(api.init(jax.random.PRNGKey(0)))
    state = init_train_state(params, hyper)
    n_params = sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"steps={args.steps} compression={hyper.compression}")

    restored, start = elastic_restore(args.ckpt_dir, jax.device_get(state))
    if restored is not None:
        state = restored
        print(f"restored checkpoint at step {start}")

    guard = PreemptionGuard()
    losses = []

    def on_metrics(s, m):
        losses.append(float(m["loss"]))
        if s % args.log_every == 0:
            print(f"step {s}: loss={losses[-1]:.4f} "
                  f"lr={float(m['lr']):.2e} gnorm={float(m['grad_norm']):.2f}",
                  flush=True)

    res = run_with_fault_tolerance(
        step, state, bat, num_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, start_step=start, guard=guard,
        on_metrics=on_metrics)
    print(f"done: steps={res.completed_steps} interrupted={res.interrupted} "
          f"final_loss={losses[-1] if losses else float('nan'):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
