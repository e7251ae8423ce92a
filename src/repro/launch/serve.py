"""Serving launcher: batched wave serving of a smoke-config model, or —
with ``--placement`` — FlexAI multi-vehicle placement serving through
the QoS wave engine (``repro.serve.qos.QoSPlacementEngine``), FIFO
admission by default, optionally sharded over every device.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b --smoke \
        --requests 8 --max-new 16

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
    PYTHONPATH=src python -m repro.launch.serve --placement --shard \
        --routes 8 --route-km 0.03

Routes arrive over a virtual timeline (``--arrival-gap``) with Table-5
deadlines (``--deadline-scale`` tightens or relaxes them).  ``--qos edf``
admits waves earliest-effective-deadline-first with aging credit,
preemption and shedding:

    PYTHONPATH=src python -m repro.launch.serve --placement --qos edf \
        --routes 8 --route-km 0.01 --arrival-gap 0.02

``--continuous`` refills freed wave lanes at segment boundaries instead
of draining, ``--measured-svc`` replaces the virtual service clock with
a measured per-bucket EMA, and ``--shard`` shards the waves' lanes over
the ``("routes",)`` mesh — bit-exact against the single-device path.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

from repro.compat import enable_compile_cache
from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models.api import model_api
from repro.serve.engine import Request, ServeEngine
from repro.sharding import unbox


def run_token_serving(args) -> int:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encoder_decoder:
        print("serve launcher currently targets decoder-only archs")
        return 1
    api = model_api(cfg)
    params = unbox(api.init(jax.random.PRNGKey(0)))
    eng = ServeEngine(api, params, slots=args.slots, max_seq=args.max_seq,
                      temperature=args.temperature,
                      qos=args.qos, deadline_scale=args.deadline_scale)
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        plen = int(rng.integers(3, 10))
        eng.submit(Request(
            uid=uid,
            prompt=rng.integers(1, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new))
    t0 = time.perf_counter()
    eng.run_until_done()
    dt = time.perf_counter() - t0
    toks = sum(len(r.generated) for r in eng.finished)
    qs = eng.qos_stats()
    print(f"served {len(eng.finished)} requests, {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    print(f"qos[{qs['policy']}]: miss_rate {qs['miss_rate']:.3f} "
          f"shed {qs['shed']} p50_slack {qs['p50_slack']:.1f} "
          f"p99_slack {qs['p99_slack']:.1f} (steps)")
    for r in eng.finished[:3]:
        print(f"  req {r.uid}: {r.generated[:8]}...")
    return 0


def _durable_mode(args) -> bool:
    """Any durability-shaped flag routes the QoS engine through
    ``DurableQoSEngine`` (snapshots / resume / fault injection / mesh)."""
    return bool(args.snapshot_dir or args.resume or args.state_out
                or args.serve_waves or args.inject_core is not None)


def _route_params(args, i: int):
    """Route ``i`` of a placement run: areas cycle UB, UHW, HW so a fleet
    mixes the paper's three driving areas (each at its own Table-5
    camera rates), and every route has its own seed."""
    from repro.core.environment import Area, EnvironmentParams
    areas = list(Area)
    return EnvironmentParams(area=areas[i % len(areas)],
                             route_km=args.route_km,
                             rate_scale=args.rate_scale, seed=args.seed + i)


def run_qos_placement_serving(args):
    """Placement serving, what every ``--placement`` run does: routes
    arrive over a virtual timeline with Table-5-derived deadlines and are
    admitted bucket-FIFO, or with ``--qos edf`` EDF with aging,
    preemption and shedding (see ``repro.serve.qos``).  Returns the
    drained engine, or None when the flags conflict (the reason is
    printed).

    Durability flags (``repro.serve.durability``): ``--snapshot-dir`` /
    ``--snapshot-every`` write crash-recovery snapshots on a segment
    cadence, ``--resume`` restores the latest one (optionally onto a
    different mesh with ``--shard``), ``--serve-waves K`` stops after K
    admission rounds (the crash-point control of the recovery tests),
    ``--inject-core/--inject-at/--inject-factor`` degrade an accelerator
    mid-run (``--no-degrade`` disables the graceful-degradation
    response), and ``--state-out`` writes the bit-exactness digest npz.
    """
    from repro.core.environment import build_task_queue
    from repro.core.flexai import FlexAIAgent, FlexAIConfig
    from repro.core.hmai import HMAIPlatform
    from repro.serve.qos import QoSConfig, QoSPlacementEngine

    durable = _durable_mode(args)
    if durable and (args.continuous or args.measured_svc):
        print("--continuous/--measured-svc are incompatible with "
              "durability flags (the snapshot format packs whole-wave "
              "checkpoints and crash replay needs the deterministic "
              "virtual clock)")
        return None
    if args.stages > 1 and durable:
        print("--stages > 1 is incompatible with durability flags "
              "(pipeline waves checkpoint (state, ring); the snapshot "
              "format and fault-masked executors are single-stage)")
        return None
    plat = HMAIPlatform(capacity_scale=args.rate_scale)
    if args.inject_core is not None and not (0 <= args.inject_core < plat.n):
        print(f"--inject-core {args.inject_core} out of range: the "
              f"platform has {plat.n} accelerators (valid: 0..{plat.n - 1})")
        return None
    if args.stages > 1:
        # stage-level placement needs stage-shaped Q params
        from repro.core.pipeline import PipelineFlexAI
        pipe = PipelineFlexAI(plat, FlexAIConfig(seed=args.seed),
                              n_stages=args.stages)
        if args.weights:
            pipe.load_weights(args.weights)
        params, backlog_scale = pipe.eval_params(), pipe.cfg.backlog_scale
    else:
        agent = FlexAIAgent(plat, FlexAIConfig(seed=args.seed))
        if args.weights:
            agent.load_weights(args.weights)
        params, backlog_scale = agent.learner.eval_p, agent.cfg.backlog_scale
    cfg = QoSConfig(policy=args.qos, deadline_scale=args.deadline_scale,
                    slots=args.slots, min_bucket=args.min_bucket,
                    stages=args.stages, continuous=args.continuous,
                    measured_svc=args.measured_svc)

    if durable:
        from repro.serve.durability import (DurableQoSEngine,
                                            FaultInjection, serving_digest)
        from repro.train.fault_tolerance import PreemptionGuard
        mesh = None
        if args.shard:
            from repro.compat import make_mesh
            n_dev = len(jax.devices())
            mesh = make_mesh((n_dev,), ("routes",))
            print(f"durable QoS mesh: {n_dev} device(s) on axis 'routes'")
        guard = PreemptionGuard()
        if args.resume:
            eng = DurableQoSEngine.restore(
                args.snapshot_dir, plat,
                backlog_scale=backlog_scale, mesh=mesh,
                guard=guard, snapshot_every=args.snapshot_every or None,
                trace=args.trace, segment_sleep=args.segment_sleep)
            print(f"resumed snapshot: now={eng.now:.4f} "
                  f"completed={len(eng.completed)} "
                  f"waves={len(eng.wave_log)}", flush=True)
        else:
            faults = []
            if args.inject_core is not None:
                faults.append(FaultInjection(
                    at_time=args.inject_at, core=args.inject_core,
                    factor=args.inject_factor,
                    handled=not args.no_degrade))
            eng = DurableQoSEngine(
                plat, params, cfg,
                backlog_scale=backlog_scale,
                snapshot_dir=args.snapshot_dir,
                snapshot_every=args.snapshot_every, faults=faults,
                mesh=mesh, guard=guard, trace=args.trace,
                segment_sleep=args.segment_sleep)
    else:
        mesh = None
        if args.shard:
            if args.stages > 1:
                print("--shard is single-stage (pipeline waves have "
                      "their own 2-D mesh path)")
                return None
            from repro.compat import make_mesh
            n_dev = len(jax.devices())
            mesh = make_mesh((n_dev,), ("routes",))
            print(f"QoS wave mesh: {n_dev} device(s) on axis 'routes'")
        eng = QoSPlacementEngine(plat, params, cfg,
                                 backlog_scale=backlog_scale, mesh=mesh)
    if args.trace:
        from repro.serve.tracing import Tracer
        eng.tracer = Tracer()

    if not args.resume:
        t = 0.0
        for i in range(args.routes):
            eng.submit(build_task_queue(_route_params(args, i)), arrival=t)
            t += args.arrival_gap
    t0 = time.perf_counter()
    if durable and args.serve_waves:
        n = eng.serve_waves(args.serve_waves)
        eng.snapshot()  # boundary snapshot so a --resume continues here
        if eng.saver is not None:
            eng.saver.wait()
        print(f"partial run: served {n} waves, snapshotted", flush=True)
    else:
        eng.run_until_done()
        if durable and eng.saver is not None:
            eng.snapshot()
            eng.saver.wait()
    dt = time.perf_counter() - t0
    if eng.tracer is not None:
        print(eng.tracer.line(), flush=True)
        eng.tracer = None
    s = eng.stats()
    print(f"qos[{s['policy']}] served {s['completed']}/{s['submitted']} "
          f"routes in {dt:.2f}s wall ({s['virtual_time_s']:.3f}s virtual): "
          f"miss_rate {s['miss_rate']:.3f} shed {s['shed']} "
          f"preemptions {s['preemptions']} refills {s['refills']} "
          f"dispatches {s['dispatches']} "
          f"p50_slack {s['p50_slack_s']:.4f}s "
          f"p99_slack {s['p99_slack_s']:.4f}s "
          f"mean_stm {s['mean_stm_rate']:.3f}")
    if durable:
        print(f"durability: snapshots {s['snapshots_written']} "
              f"segments {s['segments_done']} faults {s['faults_fired']} "
              f"masked {s['cores_masked']} "
              f"interrupted {s['interrupted']}")
        if args.state_out:
            np.savez(args.state_out, **serving_digest(eng))
            print(f"state digest -> {args.state_out}")
    return eng


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    # deadline-aware QoS (both serving modes)
    ap.add_argument("--qos", choices=["fifo", "edf"], default="fifo",
                    help="wave admission policy (edf = deadline-aware)")
    ap.add_argument("--deadline-scale", type=float, default=1.0,
                    help="scales every derived deadline budget")
    ap.add_argument("--arrival-gap", type=float, default=0.05,
                    help="virtual seconds between route arrivals "
                         "(placement)")
    # FlexAI placement serving
    ap.add_argument("--placement", action="store_true",
                    help="serve FlexAI route placements instead of tokens, "
                         "through the QoS wave engine (FIFO unless --qos)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the placement engine over all devices")
    ap.add_argument("--routes", type=int, default=8)
    ap.add_argument("--route-km", type=float, default=0.03)
    ap.add_argument("--rate-scale", type=float, default=0.05)
    ap.add_argument("--min-bucket", type=int, default=64)
    ap.add_argument("--stages", type=int, default=1,
                    help="pipeline stages per wave (>1 serves stage-level "
                         "placements via core.pipeline; incompatible with "
                         "durability flags)")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: refill freed wave lanes at "
                         "segment boundaries instead of draining "
                         "(incompatible with durability flags)")
    ap.add_argument("--measured-svc", action="store_true",
                    help="advance the serving clock by measured segment "
                         "wall time (per-bucket EMA) instead of the "
                         "deterministic virtual constant")
    ap.add_argument("--weights", type=str, default=None,
                    help="npz of trained EvalNet weights")
    ap.add_argument("--seed", type=int, default=0)
    # durability / crash recovery (repro.serve.durability)
    ap.add_argument("--snapshot-dir", type=str, default=None,
                    help="write crash-recovery snapshots here")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot cadence in service segments (0 = only "
                         "explicit boundary snapshots)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest snapshot in --snapshot-dir "
                         "instead of submitting fresh routes")
    ap.add_argument("--serve-waves", type=int, default=0,
                    help="stop after N admission rounds and snapshot "
                         "(crash-point control; 0 = run to completion)")
    ap.add_argument("--state-out", type=str, default=None,
                    help="write the serving-outcome digest npz here "
                         "(the recovery bit-exactness contract)")
    ap.add_argument("--inject-core", type=int, default=None,
                    help="fault injection: degrade this accelerator")
    ap.add_argument("--inject-at", type=float, default=0.0,
                    help="virtual-clock time the fault fires")
    ap.add_argument("--inject-factor", type=float, default=50.0,
                    help="exec-time degradation factor (large = dead)")
    ap.add_argument("--no-degrade", action="store_true",
                    help="disable the graceful-degradation response "
                         "(the no-mitigation baseline)")
    ap.add_argument("--segment-sleep", type=float, default=0.0,
                    help="wall sleep per segment (widens the kill window "
                         "for the crash-recovery subprocess test)")
    ap.add_argument("--trace", action="store_true",
                    help="placement: attach a span tracer and print its "
                         "summary at exit (per span: count, total and self "
                         "ms; transfer counters), plus per-segment, "
                         "snapshot and fault progress lines")
    args = ap.parse_args(argv)
    if not args.placement and args.arch is None:
        ap.error("--arch is required unless --placement is given")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    enable_compile_cache()
    if args.placement:
        return 0 if run_qos_placement_serving(args) is not None else 1
    return run_token_serving(args)


if __name__ == "__main__":
    sys.exit(main())
