"""Device-resident FlexAI episode engine.

The Python training/inference loop (``agent.py``) pays a host->device
roundtrip per task: one jitted Q forward for ``act`` and one ``dqn_update``
dispatch per TD step.  Here the whole route runs inside a single
``lax.scan``:

* ``make_schedule_fn``  — greedy inference: state-vector build + Q argmax +
  ``platform_step`` fused per scan step; one device dispatch per route.
* ``make_train_fn``     — epsilon-greedy act + platform step + dGvalue+dMS
  reward + device-replay write + (on the ``update_every`` cadence) an
  inlined ``dqn_td_update`` with TargNet sync, all in the scan body.
* both come with a ``jax.vmap``-ed batch variant: routes padded to a common
  length (``TaskArrays.valid`` masks the tail) so one device call schedules
  or trains N routes/seeds.

``ScanFlexAI`` is the host-side convenience wrapper mirroring
``FlexAIAgent``'s train/schedule surface on top of these functions.
See DESIGN.md ("Scan-body layout").

The jitted training episode is XLA module ``jit_train_episode`` and the
greedy eval ``jit_eval_episode``, the names by which a device trace
attributes their time.  ``ScanFlexAI.tracer = serve.tracing.Tracer()``
records the host side of each episode (spans ``episode`` >
``episode.upload``, ``episode.call``, ``episode.fetch``,
``episode.summarize``, and ``eval``) with the counters ``episodes``,
``train_steps``, ``td_updates`` and the transfer counters; ``None``, the
default, leaves ``serve.tracing.DETACHED`` in place, whose calls do
nothing.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.flatten_util  # noqa: F401  (jax.flatten_util.ravel_pytree)
import jax.numpy as jnp
import numpy as np

from repro.compat import vary_like
from repro.core.flexai.dqn import (AdamState, DQNParams, _adam_init,
                                   adam_apply, dqn_td_grads, dqn_td_update,
                                   init_qnet, qnet_apply)
from repro.core.flexai.replay import (DeviceReplay, device_replay_add,
                                      device_replay_init,
                                      device_replay_sample)
from repro.core.flexai.reward import reward_from_states
from repro.core.platform_jax import (PlatformSpec, kind_feature_table,
                                     platform_init, platform_step,
                                     spec_from_platform, state_vector,
                                     summarize, with_health)
from repro.core.tasks import (TaskArrays, pad_task_arrays,
                              stack_task_arrays, tasks_to_arrays)

def _jit_named(name: str, fn):
    """``jax.jit`` of ``fn`` as XLA module ``jit_<name>``: a stable name
    by which a device trace attributes the module's time."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)
    named.__name__ = named.__qualname__ = name
    return jax.jit(named)


# ---------------------------------------------------------------------------
# greedy inference
# ---------------------------------------------------------------------------

def _schedule_run(spec: PlatformSpec, backlog_scale: float):
    """Un-jitted single-route greedy episode: the shared core that the
    jitted, vmapped and shard_mapped entry points all wrap.

    An optional ``health`` trace ([T, n], core.faults) is installed row
    by row before each policy step: the state vector's exec column
    inflates by 1/capacity and the Q argmax is masked to alive cores.
    With no trace every row is 1.0, which divides and masks as the
    identity — placements match the pre-fault engine bit-exactly."""
    feat = jnp.asarray(kind_feature_table())

    def body(params, state, x):
        task, hrow = x
        state = with_health(state, hrow)
        sv = state_vector(spec, feat, backlog_scale, state, task)
        q = jnp.where(state.alive, qnet_apply(params, sv), -jnp.inf)
        action = jnp.argmax(q).astype(jnp.int32)
        return platform_step(spec, state, task, action)

    def run(params, tasks: TaskArrays, state0=None, health=None):
        init = platform_init(spec.n) if state0 is None else state0
        t = tasks.arrival.shape[0]
        trace = (jnp.ones((t, spec.n), jnp.float32) if health is None
                 else jnp.asarray(health, jnp.float32))
        init, trace = vary_like((init, trace), tasks.arrival)
        final, recs = jax.lax.scan(functools.partial(body, params),
                                   init, (tasks, trace))
        return final, recs

    return run


def _schedule_run_masked(spec: PlatformSpec, backlog_scale: float):
    """Greedy episode with an ``alive`` accelerator mask: dead cores are
    excluded from the Q argmax, so every placement lands on a survivor.

    This is the graceful-degradation variant of :func:`_schedule_run`
    (serve/durability.py): ``alive`` is a runtime [n] bool argument, so
    one compiled closure serves any fault pattern, and with all cores
    alive the select is the identity — placements match the unmasked
    engine bit-exactly.
    """
    feat = jnp.asarray(kind_feature_table())

    def body(params, alive, state, task):
        sv = state_vector(spec, feat, backlog_scale, state, task)
        q = jnp.where(alive, qnet_apply(params, sv), -jnp.inf)
        action = jnp.argmax(q).astype(jnp.int32)
        return platform_step(spec, state, task, action)

    def run(params, tasks: TaskArrays, state0=None, alive=None):
        init = platform_init(spec.n) if state0 is None else state0
        init = vary_like(init, tasks.arrival)
        mask = jnp.ones((spec.n,), bool) if alive is None else alive
        final, recs = jax.lax.scan(
            functools.partial(body, params, mask), init, tasks)
        return final, recs

    return run


def make_schedule_fn(spec: PlatformSpec, backlog_scale: float = 1.0,
                     batched: bool = False):
    """Compile the greedy scheduler.

    Returns ``fn(params, tasks) -> (final_state, records)``; with
    ``batched=True`` the tasks carry a leading route axis [R, T] and the
    params are shared across routes.  The single-route variant also
    accepts an optional third ``state0`` argument to resume scheduling
    from a mid-route ``PlatformState`` (the fig-14 braking continuation).
    """
    run = _schedule_run(spec, backlog_scale)
    if batched:
        single = run

        def run(params, tasks, health=None):
            # per-route fault traces vmap alongside the routes; the
            # healthy default keeps the two-arg call signature intact
            if health is None:
                return jax.vmap(single, in_axes=(None, 0))(params, tasks)
            return jax.vmap(lambda p, t, h: single(p, t, health=h),
                            in_axes=(None, 0, 0))(params, tasks, health)
    return jax.jit(run)


def make_sharded_schedule_fn(spec: PlatformSpec, mesh,
                             backlog_scale: float = 1.0,
                             axis: str = "routes"):
    """Compile the multi-device greedy scheduler: the vmapped route batch
    is split over ``mesh``'s ``axis`` with ``shard_map``, one independent
    scan per device over its local routes.

    Params replicate; the [R, T] task batch shards on the route axis, so R
    must be a multiple of the mesh size (``tasks.pad_route_batch``).  No
    collectives are involved — routes are independent — which is why the
    engine scales linearly until the per-device lane width stops covering
    the scan-step overhead.
    """
    from jax.sharding import PartitionSpec as P

    run = jax.vmap(_schedule_run(spec, backlog_scale), in_axes=(None, 0))
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(P(), P(axis)),
                            out_specs=P(axis))
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# fused training episode
# ---------------------------------------------------------------------------

class TrainState(NamedTuple):
    """Everything the fused episode mutates, as one pytree (per lane when
    vmapped): EvalNet/TargNet/Adam, the device replay ring, the epsilon /
    target-sync counters, and the PRNG key."""
    eval_p: DQNParams
    targ_p: DQNParams
    opt: AdamState
    replay: DeviceReplay
    env_steps: jax.Array   # i32: epsilon schedule position
    updates: jax.Array     # i32: TD updates done (TargNet cadence)
    key: jax.Array


def train_init(key, state_dim: int, n_actions: int,
               replay_capacity: int) -> TrainState:
    params = init_qnet(key, state_dim, n_actions)
    return TrainState(
        eval_p=params, targ_p=params, opt=_adam_init(params),
        replay=device_replay_init(replay_capacity, state_dim),
        env_steps=jnp.int32(0), updates=jnp.int32(0),
        key=jax.random.fold_in(key, 1),
    )


def _train_run(spec: PlatformSpec, cfg, td_kernel: bool = False):
    """Un-jitted single-lane fused training episode (see
    :func:`make_train_fn` for the contract).

    ``td_kernel=True`` swaps the scan body's ``dqn_td_update`` for the
    Pallas fused kernel (``repro.kernels.dqn_update``): forward, double-
    DQN target, Huber loss, hand-derived backward, global-norm clip and
    Adam in one VMEM-resident pass.  The switch is a Python-level branch,
    so the default trace is *identical* to the pre-kernel engine — the
    kernel compiles out entirely when off.

    The optional ``health`` trace makes this the *degradation trainer*:
    the greedy arm is masked to alive cores and ``platform_step`` charges
    health-scaled exec/energy, so the reward stream penalizes placements
    on throttled cores.  Random exploration stays uniform over all cores —
    the agent must *learn* to avoid degraded ones, and the PRNG stream is
    untouched, so a healthy trace reproduces the clean trainer bit-exactly
    (the DP-parity contract; the DP trainer itself stays clean-only)."""
    feat = jnp.asarray(kind_feature_table())
    n_actions = spec.n
    if td_kernel:
        from repro.kernels.dqn_update import dqn_td_update_fused
        td_update = dqn_td_update_fused
    else:
        td_update = dqn_td_update

    def body(carry, x):
        # sv rides the carry: nsv computed at step i-1 IS step i's
        # observation (same platform state, same task row), so each step
        # builds exactly one state vector instead of two.  The health row
        # lands on the *platform* before the step commits; the observation
        # sees it one step later (nsv is built from the stepped state) —
        # the action mask, not the exec column, is the fresh fault signal.
        ts, plat, sv = carry
        task, nxt_task, done, hrow = x
        plat = with_health(plat, hrow)
        key, k_eps, k_act, k_smp = jax.random.split(ts.key, 4)

        frac = jnp.minimum(
            1.0, ts.env_steps.astype(jnp.float32)
            / max(cfg.eps_decay_steps, 1))
        eps = cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac
        explore = jax.random.uniform(k_eps) < eps
        greedy = jnp.argmax(jnp.where(plat.alive,
                                      qnet_apply(ts.eval_p, sv), -jnp.inf))
        action = jnp.where(
            explore, jax.random.randint(k_act, (), 0, n_actions),
            greedy).astype(jnp.int32)

        plat2, rec = platform_step(spec, plat, task, action)
        reward = reward_from_states(spec, plat, plat2)
        nsv = state_vector(spec, feat, cfg.backlog_scale, plat2, nxt_task)

        valid = task.valid
        replay = device_replay_add(ts.replay, sv, action, reward, nsv,
                                   done.astype(jnp.float32), write=valid)
        env_steps = ts.env_steps + valid.astype(jnp.int32)
        do_update = (valid & (replay.size >= cfg.min_replay)
                     & (env_steps % cfg.update_every == 0))

        def upd(_):
            batch = device_replay_sample(replay, k_smp, cfg.batch_size)
            new_p, new_opt, loss = td_update(
                ts.eval_p, ts.targ_p, ts.opt, batch,
                gamma=cfg.gamma, lr=cfg.lr)
            updates = ts.updates + 1
            sync = (updates % cfg.target_sync_every) == 0
            targ = jax.tree_util.tree_map(
                lambda t, e: jnp.where(sync, e, t), ts.targ_p, new_p)
            return new_p, targ, new_opt, updates, loss

        def skip(_):
            return (ts.eval_p, ts.targ_p, ts.opt, ts.updates,
                    vary_like(jnp.float32(0.0), reward))

        eval_p, targ_p, opt, updates, loss = jax.lax.cond(
            do_update, upd, skip, None)
        ts2 = TrainState(eval_p=eval_p, targ_p=targ_p, opt=opt,
                         replay=replay, env_steps=env_steps,
                         updates=updates, key=key)
        return (ts2, plat2, nsv), (rec, loss, do_update)

    def run(ts: TrainState, tasks: TaskArrays, health=None):
        # S_{i+1} pairs with the *next valid* task; the last valid task
        # pairs with itself and carries done=True, matching the Python
        # loop — on padded routes the terminal transition must not
        # bootstrap from a padding row
        next_valid = jnp.concatenate(
            [tasks.valid[1:], jnp.zeros((1,), bool)])
        nxt = jax.tree_util.tree_map(
            lambda a: jnp.where(next_valid,
                                jnp.concatenate([a[1:], a[-1:]]), a),
            tasks)
        t = tasks.arrival.shape[0]
        done = jnp.arange(t) == tasks.valid.sum() - 1
        trace = (jnp.ones((t, spec.n), jnp.float32) if health is None
                 else jnp.asarray(health, jnp.float32))
        plat0, trace = vary_like((platform_init(spec.n), trace),
                                 tasks.arrival)
        sv0 = state_vector(spec, feat, cfg.backlog_scale, plat0,
                           jax.tree_util.tree_map(lambda a: a[0], tasks))
        (ts_f, plat_f, _), (recs, losses, upd_mask) = jax.lax.scan(
            body, (ts, plat0, sv0), (tasks, nxt, done, trace))
        return ts_f, plat_f, recs, losses, upd_mask

    return run


def make_train_fn(spec: PlatformSpec, cfg, batched: bool = False,
                  td_kernel: bool = False):
    """Compile the fused training episode for a ``FlexAIConfig``-shaped
    ``cfg`` (gamma, lr, batch_size, min_replay, target_sync_every,
    eps_start/end/decay_steps, update_every, backlog_scale).

    Returns ``fn(train_state, tasks) -> (train_state, platform_state,
    records, losses, update_mask)``.  ``batched=True`` vmaps over lanes:
    stacked TrainState (independent seeds) x stacked routes.
    ``td_kernel=True`` runs the TD update through the Pallas fused kernel
    (interpreted on the CPU backend; see ``repro.kernels.protocol``).
    """
    # note: no buffer donation — at init eval_p and targ_p alias the same
    # arrays, and donating an aliased pytree is an XLA error
    run = _train_run(spec, cfg, td_kernel=td_kernel)
    if batched:
        single = run

        def run(ts, tasks, health=None):
            if health is None:
                return jax.vmap(single, in_axes=(0, 0))(ts, tasks)
            return jax.vmap(lambda s, t, h: single(s, t, health=h),
                            in_axes=(0, 0, 0))(ts, tasks, health)
    return _jit_named("train_episode", run)


def make_sharded_train_fn(spec: PlatformSpec, cfg, mesh,
                          axis: str = "routes", td_kernel: bool = False):
    """Compile the multi-device fused training episode: stacked lanes
    (TrainState x routes) shard over ``mesh``'s ``axis``, each device
    training its local lanes' independent agents in one scan.

    The lane count must be a multiple of the mesh size.  Lanes never
    communicate (independent seeds, per-lane replay rings), so this is the
    population-training analogue of :func:`make_sharded_schedule_fn`.
    """
    from jax.sharding import PartitionSpec as P

    run = jax.vmap(_train_run(spec, cfg, td_kernel=td_kernel),
                   in_axes=(0, 0))
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(P(axis), P(axis)),
                            out_specs=P(axis))
    return _jit_named("train_episode", sharded)


# ---------------------------------------------------------------------------
# data-parallel fused training (one synchronized agent over route shards)
# ---------------------------------------------------------------------------

def dp_train_init(key, state_dim: int, n_actions: int, replay_capacity: int,
                  lanes: int) -> TrainState:
    """TrainState for the data-parallel trainer: ONE shared agent
    (EvalNet/TargNet/Adam/counters/key exactly as :func:`train_init`) plus
    a stacked [lanes, ...] replay ring — one ring per route lane, so each
    lane's TD batch samples its own trajectory and the gradients are
    averaged (the data-parallel global batch)."""
    params = init_qnet(key, state_dim, n_actions)
    return TrainState(
        eval_p=params, targ_p=params, opt=_adam_init(params),
        replay=jax.vmap(
            lambda _: device_replay_init(replay_capacity, state_dim)
        )(jnp.arange(lanes)),
        env_steps=jnp.int32(0), updates=jnp.int32(0),
        key=jax.random.fold_in(key, 1),
    )


def _dp_train_run(spec: PlatformSpec, cfg, lanes: int, axis=None,
                  n_shards: int = 1, chunk_collectives: bool = True,
                  td_kernel: bool = False):
    """Un-jitted data-parallel fused episode over ``lanes`` local routes.

    ``td_kernel=True`` computes each lane's clipped TD gradient with the
    Pallas fused kernel's *grads* variant — the ``(loss, grads)`` /
    ``adam_apply`` seam below is untouched, so the per-lane gradients
    still average locally and ``lax.pmean`` across the mesh axis before
    the single shared Adam step.

    Unlike :func:`_train_run` (N *independent* population agents), every
    lane — and, when ``axis`` names a mesh axis under ``shard_map``, every
    device — advances ONE synchronized agent:

    * acting / platform stepping / replay writes are per-lane (vmapped);
    * each lane samples a TD batch from its own ring, computes the clipped
      gradient, and the gradients are averaged over local lanes and
      ``lax.pmean``-ed over the mesh axis before a single shared Adam step;
    * the epsilon schedule, update cadence and TargNet sync run on *global*
      counters (``lax.psum`` of per-shard valid-task counts), so every
      shard takes the identical parameter trajectory.

    Collective layout (``chunk_collectives=True``, the default): only the
    2-float update-gate stats all-reduce every scan step; the TD batch
    sample, gradient computation, gradient all-reduce and Adam step run
    inside ``lax.cond`` on optimizer steps only (MaxText-style chunking —
    the big collective fires once per optimizer step, not once per scan
    step).  A conditioned ``pmean`` is safe here *because the predicate is
    shard-uniform by construction*: it derives solely from the psum'd
    global counters, so every shard takes the same branch and the mesh
    cannot deadlock.  ``chunk_collectives=False`` keeps the legacy layout
    (gradient computed and all-reduced every step, application masked with
    ``where``) — the two are bit-exact-trajectory equivalent at equal
    global batch (tests/test_dp_trainer.py) since the per-step PRNG splits
    are consumed identically and the kept values come from identical ops.

    With ``axis=None``, 1 lane, and the same route, the trajectory
    reproduces :func:`_train_run` (the DP parity contract in
    tests/test_dp_trainer.py): global lane 0 consumes the per-step PRNG
    keys raw, exactly like the single-lane body, while lane g > 0 folds g
    in for exploration/sampling diversity.
    """
    feat = jnp.asarray(kind_feature_table())
    n_actions = spec.n
    if td_kernel:
        from repro.kernels.dqn_update import dqn_td_grads_fused
        td_grads = dqn_td_grads_fused
    else:
        td_grads = dqn_td_grads

    if axis is None:
        psum = pmean = lambda x: x
        n_shards = 1
    else:
        psum = functools.partial(jax.lax.psum, axis_name=axis)
        pmean = functools.partial(jax.lax.pmean, axis_name=axis)

    def body(gidx, carry, x):
        ts, plats, svs = carry              # svs: step i's observations
        task, nxt_task, done = x            # leaves [lanes]
        key, k_eps, k_act, k_smp = jax.random.split(ts.key, 4)

        def lane_keys(k):
            ks = jax.vmap(lambda g: jax.random.fold_in(k, g))(gidx)
            return jnp.where((gidx == 0)[:, None], k[None, :], ks)

        frac = jnp.minimum(
            1.0, ts.env_steps.astype(jnp.float32)
            / max(cfg.eps_decay_steps, 1))
        eps = cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac

        def act_step(plat, sv, trow, nrow, ke, ka):
            explore = jax.random.uniform(ke) < eps
            greedy = jnp.argmax(qnet_apply(ts.eval_p, sv))
            action = jnp.where(
                explore, jax.random.randint(ka, (), 0, n_actions),
                greedy).astype(jnp.int32)
            plat2, rec = platform_step(spec, plat, trow, action)
            reward = reward_from_states(spec, plat, plat2)
            nsv = state_vector(spec, feat, cfg.backlog_scale, plat2, nrow)
            return plat2, rec, action, reward, nsv

        plats2, recs, actions, rewards, nsvs = jax.vmap(act_step)(
            plats, svs, task, nxt_task, lane_keys(k_eps), lane_keys(k_act))
        replay = jax.vmap(device_replay_add)(
            ts.replay, svs, actions, rewards, nsvs,
            done.astype(jnp.float32), task.valid)

        def td_batch():
            batches = jax.vmap(
                lambda b, k: device_replay_sample(b, k, cfg.batch_size)
            )(replay, lane_keys(k_smp))
            # differentiate a shard-local (varying) view of the shared
            # weights: the gradient of the replicated ones would already
            # be psum'd across shards inside the backward pass, ahead of
            # the explicit pmean below
            eval_p, targ_p = vary_like((ts.eval_p, ts.targ_p), replay.size)
            return jax.vmap(
                lambda b: td_grads(eval_p, targ_p, b,
                                   gamma=cfg.gamma))(batches)

        # cadence = update_every-boundary CROSSING, not an exact-multiple
        # check: env_steps advances by the global valid-lane count per
        # scan step, so `env_steps % update_every == 0` would alias
        # (e.g. 4 lanes with update_every=3 lands on a multiple only
        # every third step — a 6x silent under-training).  For one lane
        # the crossing test reduces exactly to the single-lane modulo.
        if chunk_collectives:
            # only the 2-float gate stats all-reduce every step; the
            # gradient collective + Adam step wait for an optimizer step.
            # The cond predicate is shard-uniform (pure function of the
            # psum'd globals), so the conditional pmean cannot deadlock.
            stats = psum(jnp.stack([
                task.valid.astype(jnp.float32).sum(),
                (replay.size.min() >= cfg.min_replay).astype(jnp.float32),
            ]))
            env_steps = ts.env_steps + stats[0].astype(jnp.int32)
            crossed = (env_steps // cfg.update_every
                       > ts.env_steps // cfg.update_every)
            do_update = crossed & (stats[1] == float(n_shards))

            def upd(_):
                losses, grads = td_batch()
                flat, unravel = jax.flatten_util.ravel_pytree(
                    (losses.mean(),
                     jax.tree_util.tree_map(lambda g: g.mean(0), grads)))
                gloss, g = unravel(pmean(flat))
                new_p, new_opt = adam_apply(ts.eval_p, ts.opt, g, lr=cfg.lr)
                return new_p, new_opt, gloss

            def skip(_):
                return ts.eval_p, ts.opt, jnp.float32(0.0)

            eval_p, opt, loss = jax.lax.cond(do_update, upd, skip, None)
        else:
            # legacy layout: ONE collective per scan step — the update-gate
            # counters ride the gradient pmean as f32 (pre-scaled by
            # n_shards: pmean(x * n) == psum(x), exact in f32 for these
            # small integers) and the application is where-masked
            losses, grads = td_batch()
            stats = jnp.stack([
                task.valid.astype(jnp.float32).sum(),
                (replay.size.min() >= cfg.min_replay).astype(jnp.float32),
            ]) * float(n_shards)
            flat, unravel = jax.flatten_util.ravel_pytree(
                (stats, losses.mean(),
                 jax.tree_util.tree_map(lambda g: g.mean(0), grads)))
            stats, loss, grads = unravel(pmean(flat))
            env_steps = ts.env_steps + stats[0].astype(jnp.int32)
            crossed = (env_steps // cfg.update_every
                       > ts.env_steps // cfg.update_every)
            do_update = crossed & (stats[1] == float(n_shards))
            new_p, new_opt = adam_apply(ts.eval_p, ts.opt, grads, lr=cfg.lr)
            keep = lambda n, o: jnp.where(do_update, n, o)  # noqa: E731
            eval_p = jax.tree_util.tree_map(keep, new_p, ts.eval_p)
            opt = jax.tree_util.tree_map(keep, new_opt, ts.opt)
            loss = jnp.where(do_update, loss, 0.0)

        updates = ts.updates + do_update.astype(jnp.int32)
        sync = do_update & (updates % cfg.target_sync_every == 0)
        targ_p = jax.tree_util.tree_map(
            lambda e, t: jnp.where(sync, e, t), eval_p, ts.targ_p)
        ts2 = TrainState(eval_p=eval_p, targ_p=targ_p, opt=opt,
                         replay=replay, env_steps=env_steps,
                         updates=updates, key=key)
        return (ts2, plats2, nsvs), (recs, loss, do_update)

    def run(ts: TrainState, tasks: TaskArrays):
        # global lane ids: shard i owns contiguous lanes [i*lanes, ...)
        # (shard_map block partitioning); global lane 0 keeps the raw
        # per-step keys so the 1-shard trajectory matches _train_run
        base = 0 if axis is None else jax.lax.axis_index(axis) * lanes
        gidx = base + jnp.arange(lanes)
        next_valid = jnp.concatenate(
            [tasks.valid[:, 1:], jnp.zeros((lanes, 1), bool)], axis=1)
        nxt = jax.tree_util.tree_map(
            lambda a: jnp.where(
                next_valid,
                jnp.concatenate([a[:, 1:], a[:, -1:]], axis=1), a),
            tasks)
        t = tasks.arrival.shape[1]
        done = jnp.arange(t)[None, :] == \
            tasks.valid.sum(axis=1, keepdims=True) - 1
        plats0 = vary_like(
            jax.vmap(lambda _: platform_init(spec.n))(jnp.arange(lanes)),
            tasks.arrival)
        svs0 = jax.vmap(
            lambda p, trow: state_vector(spec, feat, cfg.backlog_scale,
                                         p, trow)
        )(plats0, jax.tree_util.tree_map(lambda a: a[:, 0], tasks))
        xs = jax.tree_util.tree_map(
            lambda a: jnp.swapaxes(a, 0, 1), (tasks, nxt, done))
        (ts_f, plat_f, _), (recs, losses, upd) = jax.lax.scan(
            functools.partial(body, gidx), (ts, plats0, svs0), xs)
        recs = jax.tree_util.tree_map(
            lambda a: jnp.swapaxes(a, 0, 1), recs)
        return ts_f, plat_f, recs, losses, upd

    return run


def make_dp_train_fn(spec: PlatformSpec, cfg, lanes: int, mesh=None,
                     axis: str = "routes", chunk_collectives: bool = True,
                     td_kernel: bool = False):
    """Compile the data-parallel fused trainer.

    Returns ``fn(train_state, tasks) -> (train_state, platform_states,
    records, losses, update_mask)`` where ``train_state`` comes from
    :func:`dp_train_init` (shared agent + [lanes, ...] replay) and
    ``tasks`` is a [lanes, T] route batch — the data-parallel global
    batch.  ``records`` / ``platform_states`` keep the [lanes, ...] route
    axis; ``losses`` / ``update_mask`` are [T], shared by construction.

    With ``mesh``, the lane axis shards over ``mesh``'s ``axis``
    (``lanes`` must be a multiple of the mesh size): each device runs its
    local routes and the per-step gradient all-reduce keeps every shard on
    one synchronized agent — the scale-out recipe of MaxText-style JAX
    trainers, on the platform substrate — and with the default
    ``chunk_collectives=True`` the gradient all-reduce fires once per
    optimizer step instead of every scan step (see ``_dp_train_run``).
    """
    if mesh is None:
        return _jit_named("train_episode", _dp_train_run(
            spec, cfg, lanes, chunk_collectives=chunk_collectives,
            td_kernel=td_kernel))
    from jax.sharding import PartitionSpec as P

    if lanes < 1 or lanes % mesh.size:
        raise ValueError(f"lanes={lanes} must be a positive multiple of "
                         f"the mesh size {mesh.size}")
    run = _dp_train_run(spec, cfg, lanes // mesh.size, axis=axis,
                        n_shards=mesh.size,
                        chunk_collectives=chunk_collectives,
                        td_kernel=td_kernel)
    ts_specs = TrainState(eval_p=P(), targ_p=P(), opt=P(), replay=P(axis),
                          env_steps=P(), updates=P(), key=P())
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(ts_specs, P(axis)),
                            out_specs=(ts_specs, P(axis), P(axis), P(), P()))
    return _jit_named("train_episode", sharded)


# ---------------------------------------------------------------------------
# host-side wrapper
# ---------------------------------------------------------------------------

class ScanFlexAI:
    """FlexAI with the device-resident engine: ``FlexAIAgent``'s surface
    (train over queues, greedy schedule, weight import/export) at one
    device dispatch per route — or per route *batch* with ``lanes > 1``.

    Two multi-lane training modes:

    * ``dp=False`` (default): ``lanes`` *independent* population agents,
      one per lane (N seeds x N routes per device call).  With ``mesh``
      (a 1-D device mesh) the lane batch shards over the mesh.
    * ``dp=True``: ONE synchronized agent trained data-parallel over a
      ``lanes``-route global batch (per-lane TD gradients averaged, and —
      with ``mesh`` — ``lax.pmean``-ed across devices each step).

    ``td_kernel=True`` routes every TD update through the Pallas fused
    kernel (``repro.kernels.dqn_update``): single-lane/population paths
    use the Adam-folded variant, the DP path the grads variant ahead of
    its ``pmean`` + shared ``adam_apply``.  Default off — the flag is a
    trace-time Python branch, so the kernel compiles out entirely and
    the default trainer stays bit-identical to the pre-kernel engine.
    On the CPU backend the kernel runs in Pallas interpret mode (slower
    there — honest numbers in BENCH_kernels.json); on a TPU it compiles.
    """

    def __init__(self, platform, cfg, lanes: int = 1, mesh=None,
                 dp: bool = False, td_kernel: bool = False):
        self.cfg = cfg
        self.spec = spec_from_platform(platform)
        self.n_actions = platform.n
        self.state_dim = 3 + 5 * platform.n
        self.lanes = lanes
        self.mesh = mesh
        self.dp = dp
        self.td_kernel = td_kernel
        key = jax.random.PRNGKey(cfg.seed)
        if dp:
            self.ts = dp_train_init(key, self.state_dim, self.n_actions,
                                    cfg.replay_capacity, lanes)
            self._train_fn = make_dp_train_fn(
                self.spec, cfg, lanes, mesh=mesh,
                axis=mesh.axis_names[0] if mesh is not None else "routes",
                td_kernel=td_kernel)
        elif lanes == 1:
            self.ts = train_init(key, self.state_dim, self.n_actions,
                                 cfg.replay_capacity)
        else:
            self.ts = jax.vmap(
                lambda k: train_init(k, self.state_dim, self.n_actions,
                                     cfg.replay_capacity)
            )(jax.random.split(key, lanes))
        if not dp:
            if mesh is not None:
                # lanes == 1 keeps an unstacked TrainState, which the
                # vmapped sharded runner cannot consume — and a sharded
                # single lane is pointless anyway
                if lanes < 2 or lanes % mesh.size:
                    raise ValueError(
                        f"lanes={lanes} must be >= 2 and a multiple of the "
                        f"mesh size {mesh.size} (omit mesh for single-lane)")
                self._train_fn = make_sharded_train_fn(
                    self.spec, cfg, mesh, axis=mesh.axis_names[0],
                    td_kernel=td_kernel)
            else:
                self._train_fn = make_train_fn(self.spec, cfg,
                                               batched=lanes > 1,
                                               td_kernel=td_kernel)
        self._sched_fn = make_schedule_fn(self.spec, cfg.backlog_scale)
        self._eval_fn = None
        self.losses: list[float] = []
        self.best_eval_stm: float | None = None
        # model-selection state lives on the instance (not train() locals)
        # so a snapshot/resume cycle keeps the best-so-far candidate
        self._best_stm: float = -1.0
        self._best_params: DQNParams | None = None
        # the last episode's (records, losses, update_mask), on the host
        self.last_episode = None
        # serve.tracing imports the serve package, which imports this
        # module: bound here, not at the top
        from repro.serve.tracing import DETACHED
        self._detached = self._tracer = DETACHED

    @property
    def tracer(self):
        """The attached ``serve.tracing.Tracer``, or None (off)."""
        return None if self._tracer is self._detached else self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        self._tracer.detach()
        self._tracer = self._detached if tracer is None else tracer
        self._tracer.attach()

    def _as_arrays(self, tasks) -> TaskArrays:
        return tasks if isinstance(tasks, TaskArrays) else \
            tasks_to_arrays(tasks)

    def train_episode(self, tasks, health=None) -> dict:
        """One fused episode (single-lane) or one episode per lane
        (``tasks`` as a list of routes / stacked TaskArrays).

        ``health`` is an optional fault trace — [T, n] single-lane,
        [lanes, T, n] for population lanes — consumed by the degradation
        trainer (core.faults); the DP and sharded trainers are clean-only.
        """
        if health is not None and (self.dp or self.mesh is not None):
            raise ValueError(
                "fault-trace training is supported on the single-host "
                "population trainer only (not dp/mesh)")
        if self.lanes > 1:
            ta = tasks if isinstance(tasks, TaskArrays) else \
                stack_task_arrays([self._as_arrays(q) for q in tasks])
        else:
            ta = self._as_arrays(tasks)
            if self.dp:  # the DP runner always carries a [lanes, T] axis
                ta = TaskArrays(*[np.asarray(f)[None] for f in ta])
        tr = self._tracer
        with tr.span("episode"):
            with tr.span("episode.upload"):
                tr.to_device(ta)
                ta = jax.device_put(ta)
            with tr.span("episode.call"):
                if health is None:
                    out = self._train_fn(self.ts, ta)
                else:
                    out = self._train_fn(
                        self.ts, ta, health=jnp.asarray(health, jnp.float32))
                jax.block_until_ready(out)
            self.ts, plat, recs, losses, upd = out
            with tr.span("episode.fetch"):
                tr.to_host((recs, losses, upd))
                recs, losses, upd = jax.device_get((recs, losses, upd))
            upd = np.asarray(upd, bool)
            self.last_episode = (recs, losses, upd)
            tr.count("episodes")
            tr.count("train_steps", int(np.asarray(recs.valid).sum()))
            tr.count("td_updates", int(upd.sum()))
            with tr.span("episode.summarize"):
                return self._summarize_episode(plat, recs, losses, upd)

    def _summarize_episode(self, plat, recs, losses, upd) -> dict:
        if upd.any():
            self.losses.extend(losses[upd].tolist())
        if self.dp:
            mean_loss = float(losses[upd].mean()) if upd.any() else None
            summ = [summarize(
                self.spec,
                jax.tree_util.tree_map(lambda a, i=i: a[i], plat),
                jax.tree_util.tree_map(lambda a, i=i: a[i], recs))
                for i in range(self.lanes)]
            if self.lanes == 1:
                s = summ[0]
                s["mean_loss"] = mean_loss
                return s
            return {"lanes": summ, "mean_loss": mean_loss}
        if self.lanes > 1:
            summ = []
            for i in range(self.lanes):
                lane = summarize(
                    self.spec,
                    jax.tree_util.tree_map(lambda a, i=i: a[i], plat),
                    jax.tree_util.tree_map(lambda a, i=i: a[i], recs))
                m = upd[i]
                lane["mean_loss"] = (float(losses[i][m].mean())
                                     if m.any() else None)
                summ.append(lane)
            return {"lanes": summ}
        s = summarize(self.spec, plat, recs)
        s["mean_loss"] = float(losses[upd].mean()) if upd.any() else None
        return s

    def train(self, queues: list, episodes: int, eval_queue=None,
              eval_every: int = 5, on_episode=None,
              start_episode: int = 0) -> list:
        """Cycle the queue pool; with ``lanes > 1`` each episode consumes
        the next ``lanes`` routes round-robin, one per lane.

        With ``eval_queue``, periodically runs a vmapped greedy eval on
        the held-out queue between fused episode segments and keeps the
        best-eval EvalNet weights (the scan-path counterpart of
        ``FlexAIAgent.train``'s model selection); the winner is restored
        into EvalNet/TargNet once training ends.

        ``on_episode(ep, trainer)`` fires after each episode (snapshot
        cadence hook, after that episode's eval); returning True ends the
        loop there, as if ``episodes`` had been reached — a caller with a
        clock closes its window on an episode boundary.  ``start_episode``
        resumes mid-run — route cycling
        and the eval cadence are indexed by the *global* episode number,
        so a restored run consumes exactly the episodes the uninterrupted
        run would have (the bit-exact resume contract; model-selection
        state rides on ``self._best_stm`` / ``self._best_params`` and is
        the restorer's to reinstall).
        """
        routes = [self._as_arrays(q) for q in queues]
        if self.lanes > 1 or self.dp:
            # shared static length -> one compiled episode per lane batch.
            # Single-lane pools stay unpadded: padding rows are training
            # no-ops but still consume per-step PRNG splits, which would
            # shift the exploration stream of every later episode.
            t_max = max(r.arrival.shape[-1] for r in routes)
            routes = [pad_task_arrays(r, t_max)
                      if r.arrival.shape[-1] < t_max else r
                      for r in routes]
        ta_eval = self._as_arrays(eval_queue) \
            if eval_queue is not None else None
        history = []
        if start_episode == 0:
            self._best_stm, self._best_params = -1.0, None
        per_lane = 1 if (self.lanes == 1 and not self.dp) else self.lanes
        for ep in range(start_episode, episodes):
            if per_lane == 1:
                history.append(self.train_episode(routes[ep % len(routes)]))
            else:
                lane_routes = [
                    routes[(ep * per_lane + i) % len(routes)]
                    for i in range(per_lane)]
                history.append(self.train_episode(lane_routes))
            if ta_eval is not None and (ep + 1) % eval_every == 0:
                with self._tracer.span("eval"):
                    stms = self._eval_stms(ta_eval)
                history[-1]["eval_stm"] = (
                    stms[0] if len(stms) == 1 else stms)
                lane = int(np.argmax(stms))
                if stms[lane] > self._best_stm:
                    self._best_stm = stms[lane]
                    self._best_params = self.eval_params(lane)
            if on_episode is not None and on_episode(ep, self) is True:
                break
        if self._best_params is not None:
            self.set_params(self._best_params)
            self.best_eval_stm = self._best_stm
        return history

    def _eval_stms(self, ta_eval: TaskArrays) -> list[float]:
        """Greedy STM rate on the held-out queue, per candidate parameter
        set: one entry for the shared agent (single-lane / DP), one per
        lane for population training (params vmapped over lanes, queue
        broadcast — a single device dispatch either way)."""
        shared = self.dp or self.lanes == 1
        if self._eval_fn is None:
            run = _schedule_run(self.spec, self.cfg.backlog_scale)
            if not shared:
                run = jax.vmap(run, in_axes=(0, None))
            self._eval_fn = _jit_named("eval_episode", run)
        if shared:
            final, recs = self._eval_fn(self.eval_params(), ta_eval)
            return [summarize(self.spec, final, recs)["stm_rate"]]
        finals, recs = self._eval_fn(self.ts.eval_p, ta_eval)
        return [summarize(
            self.spec,
            jax.tree_util.tree_map(lambda a, i=i: a[i], finals),
            jax.tree_util.tree_map(lambda a, i=i: a[i], recs))["stm_rate"]
            for i in range(self.lanes)]

    def eval_params(self, lane: int = 0) -> DQNParams:
        if self.dp or self.lanes == 1:
            return self.ts.eval_p
        return jax.tree_util.tree_map(lambda a: a[lane], self.ts.eval_p)

    # ------------------------------------------------------------------
    # weight interop with FlexAIAgent (shared npz checkpoint format)
    # ------------------------------------------------------------------

    def set_params(self, params: DQNParams) -> None:
        """Install EvalNet weights (TargNet synced, Adam reset — importing
        mid-run optimizer moments across trainers is meaningless).  With
        population lanes the weights broadcast to every lane."""
        if self.dp or self.lanes == 1:
            eval_p = params
        else:
            eval_p = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a, (self.lanes,) + a.shape).copy(),
                params)
        self.ts = self.ts._replace(
            eval_p=eval_p, targ_p=eval_p,
            opt=jax.tree_util.tree_map(jnp.zeros_like, self.ts.opt))

    @classmethod
    def from_agent(cls, agent, platform, *, lanes: int = 1, mesh=None,
                   dp: bool = False, td_kernel: bool = False,
                   cfg=None) -> "ScanFlexAI":
        """Lossless import of a ``FlexAIAgent``: same config (unless
        overridden), same EvalNet/TargNet weights, ready to continue
        training on the fused path."""
        trainer = cls(platform, cfg if cfg is not None else agent.cfg,
                      lanes=lanes, mesh=mesh, dp=dp, td_kernel=td_kernel)
        trainer.set_params(agent.learner.eval_p)
        trainer.losses = list(agent.losses)
        return trainer

    def to_agent(self, platform, lane: int = 0):
        """Lossless export to a ``FlexAIAgent`` (the Python-loop wrapper):
        the greedy policy — and therefore every placement — is preserved
        bit-exactly."""
        from repro.core.flexai.agent import FlexAIAgent
        agent = FlexAIAgent(platform, self.cfg)
        params = self.eval_params(lane)
        agent.learner.eval_p = params
        agent.learner.targ_p = params
        agent.losses = list(self.losses)
        return agent

    def save_weights(self, path: str, lane: int = 0) -> None:
        """``FlexAIAgent.save_weights``-compatible npz (p0..p5 arrays,
        one shared serializer in ``dqn.py``)."""
        from repro.core.flexai.dqn import save_dqn_npz
        save_dqn_npz(path, self.eval_params(lane))

    def load_weights(self, path: str) -> None:
        from repro.core.flexai.dqn import load_dqn_npz
        self.set_params(load_dqn_npz(path))

    def schedule(self, tasks, lane: int = 0, health=None) -> dict:
        ta = self._as_arrays(tasks)
        t0 = time.perf_counter()
        if health is None:
            final, recs = self._sched_fn(self.eval_params(lane), ta)
        else:
            final, recs = self._sched_fn(
                self.eval_params(lane), ta,
                health=jnp.asarray(health, jnp.float32))
        jax.block_until_ready(final)
        dt = time.perf_counter() - t0
        summ = summarize(self.spec, final, recs)
        summ["schedule_time_s"] = dt
        summ["schedule_time_per_task_s"] = dt / max(ta.num_tasks, 1)
        summ["placements"] = np.asarray(recs.action)
        return summ
