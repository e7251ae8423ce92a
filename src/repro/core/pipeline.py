"""Pipeline parallelism over the heterogeneous mesh.

The scan engines up to PR 6 place every chunk task *whole* on one
accelerator — pure data parallelism over routes.  This module refactors
the substrate to "one DAG -> pipeline stages -> accelerator groups"
(alpa-style inter-op parallelism, on the platform simulator):

* ``build_stage_plan`` — the stage-construction pass: MAC-balanced layer
  windows per kind (``tasks.stage_layer_stats``) are turned into per-stage
  exec/energy tables via architecture-affinity *share profiles*, and the
  accelerators are partitioned into stage groups by an exact bottleneck
  search over arch-class count compositions.
* ``_pipeline_run`` — the flattened single-device wavefront: one
  ``lax.scan`` over ``(task, stage)`` steps in wavefront-column order,
  with a finish *ring* carrying the producer->consumer edge (stage s of
  task k starts no earlier than stage s-1's finish plus the boundary
  reshard latency).
* ``make_sharded_pipeline_fn`` — the same wavefront over a 2-D
  ``("stages", "routes")`` mesh: each stage group runs on its own device
  shard and the finish ring travels through ``lax.ppermute`` — the
  cross-mesh resharding collective.  Bit-exact against the flattened
  engine (group-masked policies, order-independent observations).
* ``_pipeline_reference_run`` — the unpipelined task-major reference
  (stages unrolled per task): the parity oracle for both engines.
* stage-level FlexAI: the action space places *stages*; the observation
  (``platform_jax.stage_state_vector``, ``4 + 6n``) gains stage-occupancy
  features and a group-membership mask.  Scan (single-lane / population)
  and data-parallel (chunked-collective) training paths mirror
  ``flexai/engine.py``; ``PipelineFlexAI`` is the host wrapper.

Why per-stage shares differ per architecture: Table 8 gives whole-model
exec times only, so stage times are modeled as ``share(arch, stage, kind)
* exec(arch, kind)`` where the share comes from per-layer MACs weighted by
an arch-affinity efficiency profile (SconvOD favors large-spatial early
conv, MconvMC favors many-channel late layers, SconvIC is neutral).  The
shares sum to 1 over stages, so no accelerator is ever made faster in
aggregate — pipeline wins only by steering each stage to the group whose
architecture is strong on those layers.  See DESIGN.md ("Pipeline
parallelism over the heterogeneous mesh").
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import NamedTuple

import jax
import jax.flatten_util  # noqa: F401  (jax.flatten_util.ravel_pytree)
import jax.numpy as jnp
import numpy as np

from repro.compat import vary_like
from repro.core.flexai.dqn import (DQNParams, adam_apply, dqn_td_grads,
                                   dqn_td_update, qnet_apply)
from repro.core.flexai.engine import TrainState, dp_train_init, train_init
from repro.core.flexai.replay import device_replay_add, device_replay_sample
from repro.core.flexai.reward import reward_from_states
from repro.core.platform_jax import (PlatformSpec, PlatformState,
                                     health_capacity, kind_feature_table,
                                     platform_init, platform_step,
                                     spec_from_platform, stage_state_vector,
                                     state_vector, summarize, with_health)
from repro.core.tasks import (KIND_ORDER, TABLE5_FPS, TaskArrays,
                              _model_stats, pad_task_arrays,
                              stack_task_arrays, stage_layer_stats,
                              tasks_to_arrays)

# Cross-stage link bandwidth for the reshard latency model (bytes/s).
# Activation payloads are sub-MB (tasks.stage_layer_stats), so at 16 GB/s
# the boundary hop is tens of microseconds — real but small next to
# capacity-scaled exec times, exactly the regime that makes inter-op
# pipelining worthwhile.
DEFAULT_LINK_BYTES_PER_S = 16e9


class StagePlan(NamedTuple):
    """Static output of the stage-construction pass (not scanned over).

    * ``stage_exec`` / ``stage_energy`` [S, n, K]: per-stage views of the
      platform tables; summing over S recovers the whole-model tables
      bit-for-nearly (shares sum to 1 in f64 before the f32 product).
    * ``groups`` [n] i32: accelerator -> stage group id.
    * ``group_mask`` [S, n] bool: row s flags stage s's accelerators.
    * ``mac_frac`` [S, K] f32: MAC fraction of stage s for each kind.
    * ``reshard_s`` [S, K] f32: seconds to move kind k's activation over
      the stage boundary AFTER stage s (last row is 0 — the output stays).
    """
    stage_exec: jax.Array
    stage_energy: jax.Array
    groups: jax.Array
    group_mask: jax.Array
    mac_frac: jax.Array
    reshard_s: jax.Array

    @property
    def n_stages(self) -> int:
        return self.stage_exec.shape[0]

    @property
    def n(self) -> int:
        return self.stage_exec.shape[1]


def stage_state_dim(n: int) -> int:
    """Observation width of the stage-placement agent (see
    ``platform_jax.stage_state_vector``)."""
    return 4 + 6 * n


def _layer_eff(arch: str, layer: dict) -> float:
    """Relative efficiency of ``arch`` on one layer, in (0, 1].

    The §5 taxonomy: SconvOD is the object-detection systolic array —
    strongest on large-spatial-reuse early conv, weak once feature maps
    shrink; MconvMC is the many-channel design — strongest on
    channel-heavy late conv / fc; SconvIC sits in between (neutral).
    ``w = macs / eff`` inflates the layers an arch is weak on, which is
    what skews its per-stage share away from the plain MAC fraction.
    """
    hw_out = layer.get("hw", 1) // max(layer.get("stride", 1), 1)
    if arch == "SconvOD":
        return float(np.clip(hw_out / 48.0, 0.25, 1.0))
    if arch == "MconvMC":
        return float(np.clip(layer.get("c_in", 1) / 256.0, 0.30, 1.0))
    return 0.65


@functools.lru_cache(maxsize=32)
def stage_share_table(arch_names: tuple, n_stages: int) -> np.ndarray:
    """[n_accel, S, K] share of each kind's exec time spent in each stage,
    per accelerator.  Rows sum to 1 over S (computed in f64), so
    ``share * exec_table`` decomposes — never rescales — Table 8."""
    splits, _, _ = stage_layer_stats(n_stages)
    stats = _model_stats()
    share = np.zeros((len(arch_names), n_stages, len(KIND_ORDER)),
                     np.float32)
    for ai, arch in enumerate(arch_names):
        for ki, kind in enumerate(KIND_ORDER):
            per_layer = stats[kind.value]["per_layer"]
            w = np.asarray(
                [l["macs"] / _layer_eff(arch, l) for l in per_layer],
                np.float64)
            tot = w.sum()
            for s in range(n_stages):
                lo, hi = int(splits[ki, s]), int(splits[ki, s + 1])
                share[ai, s, ki] = w[lo:hi].sum() / tot
    return share


def assign_stage_groups(arch_names: tuple, stage_exec: np.ndarray,
                        kind_weights: np.ndarray) -> np.ndarray:
    """Exact bottleneck-optimal partition of accelerators into stage
    groups.

    Same-arch accelerators are interchangeable, so the search enumerates
    *count compositions* per arch class (how many of each class serve each
    stage) instead of the 11^S assignment space — ~10^2..10^4 candidates.
    Score = min over stages of the group's aggregate service rate
    ``sum 1/tbar`` where ``tbar`` is the kind-mix-weighted stage time; the
    argmax is the steady-state pipeline throughput bound.
    """
    S = stage_exec.shape[0]
    classes: dict = {}
    for i, nm in enumerate(arch_names):
        classes.setdefault(nm, []).append(i)
    cls_names = sorted(classes)
    w = np.asarray(kind_weights, np.float64)
    tbar = (stage_exec.astype(np.float64) * w[None, None, :]).sum(-1)

    def comps(m: int, k: int):
        if k == 1:
            yield (m,)
            return
        for first in range(m + 1):
            for rest in comps(m - first, k - 1):
                yield (first,) + rest

    best = None
    for combo in itertools.product(
            *[list(comps(len(classes[nm]), S)) for nm in cls_names]):
        counts = np.asarray(combo)                       # [n_cls, S]
        if (counts.sum(0) == 0).any():
            continue
        rate = np.zeros(S)
        for ci, nm in enumerate(cls_names):
            rate += counts[ci] / tbar[:, classes[nm][0]]
        score = rate.min()
        if best is None or score > best[0]:
            best = (score, counts)
    if best is None:
        raise ValueError(
            f"cannot form {S} non-empty stage groups from "
            f"{len(arch_names)} accelerators")
    counts = best[1]
    groups = np.zeros(len(arch_names), np.int64)
    for ci, nm in enumerate(cls_names):
        members, off = classes[nm], 0
        for s in range(S):
            for _ in range(int(counts[ci, s])):
                groups[members[off]] = s
                off += 1
    return groups.astype(np.int32)


def build_stage_plan(platform, n_stages: int, groups=None,
                     link_bytes_per_s: float = DEFAULT_LINK_BYTES_PER_S,
                     kind_weights=None) -> StagePlan:
    """Stage-construction pass: ``HMAIPlatform`` + stage count ->
    :class:`StagePlan`.  ``groups`` overrides the partition search with an
    explicit [n] stage-id assignment."""
    if n_stages < 1:
        raise ValueError(f"n_stages must be >= 1, got {n_stages}")
    arch_names = tuple(s.name for s in platform.specs)
    exec_table = np.asarray(platform.exec_time_table, np.float32)
    energy_table = np.asarray(platform.energy_table, np.float32)
    share = stage_share_table(arch_names, n_stages)      # [n, S, K]
    stage_exec = np.swapaxes(share, 0, 1) * exec_table[None]
    stage_energy = np.swapaxes(share, 0, 1) * energy_table[None]
    if kind_weights is None:
        kw = np.asarray([TABLE5_FPS[k] for k in KIND_ORDER], np.float64)
        kind_weights = kw / kw.sum()
    if groups is None:
        groups = assign_stage_groups(arch_names, stage_exec, kind_weights)
    groups = np.asarray(groups, np.int32)
    if groups.shape != (len(arch_names),):
        raise ValueError(f"groups must be [{len(arch_names)}]")
    present = np.unique(groups)
    if present.min() < 0 or present.max() >= n_stages or \
            len(present) != n_stages:
        raise ValueError(
            f"groups must cover every stage id in [0, {n_stages})")
    _, frac, act = stage_layer_stats(n_stages)           # [K, S] each
    reshard = act.T.astype(np.float32) / float(link_bytes_per_s)
    mask = groups[None, :] == np.arange(n_stages)[:, None]
    return StagePlan(
        stage_exec=jnp.asarray(stage_exec, jnp.float32),
        stage_energy=jnp.asarray(stage_energy, jnp.float32),
        groups=jnp.asarray(groups),
        group_mask=jnp.asarray(mask),
        mac_frac=jnp.asarray(frac.T, jnp.float32),
        reshard_s=jnp.asarray(reshard))


def stage_spec(spec: PlatformSpec, plan: StagePlan, s) -> PlatformSpec:
    """Per-stage view of the platform tables.  ``platform_step`` runs on
    it unchanged — a stage sub-task is just a task with stage-sized
    exec/energy columns.  The gvalue scales stay whole-model so rewards
    and summaries remain comparable across stage counts."""
    return PlatformSpec(
        exec_time=plan.stage_exec[s], energy=plan.stage_energy[s],
        gvalue_e_scale=spec.gvalue_e_scale,
        gvalue_t_scale=spec.gvalue_t_scale)


def _stage_task_view(plan: StagePlan, ring: jax.Array, row: TaskArrays,
                     s) -> TaskArrays:
    """Rewrite one task row as its stage-``s`` sub-task.

    Arrival becomes the upstream stage's finish (the ring entry written
    one wavefront column earlier) plus the boundary reshard latency, and
    the safety budget shrinks by the induced delay — so the FINAL stage's
    ``met`` is exactly the end-to-end deadline check.
    """
    prev = jnp.maximum(s - 1, 0)
    arrival = jnp.where(jnp.equal(s, 0), row.arrival,
                        ring[prev] + plan.reshard_s[prev, row.kind])
    return row._replace(arrival=arrival,
                        safety=row.safety - (arrival - row.arrival))


# ---------------------------------------------------------------------------
# placement policies (shared by every engine; all group-masked)
# ---------------------------------------------------------------------------

def _make_policy(policy: str, spec: PlatformSpec, plan: StagePlan,
                 backlog_scale: float):
    """``act(params, sp, state, trow, s) -> action`` closures.

    * ``"eft"``    — earliest finish time within the stage group (the
      heuristic baseline; params ignored).
    * ``"flexai"`` — greedy stage-placement Q argmax, masked to the group.
    * ``"task"``   — the ORIGINAL task-level observation + unmasked argmax
      (``_schedule_run``'s body verbatim).  Only meaningful with a 1-stage
      plan, where it makes the pipeline engines reproduce the existing
      data-parallel engine bit-exactly (the equivalence test).
    """
    feat = jnp.asarray(kind_feature_table())

    if policy == "eft":
        def act(params, sp, state, trow, s):
            # health-effective finish times: dead cores pay 1/HEALTH_FLOOR
            # so the argmin routes around them without shrinking the group
            # mask (an all-dead group still yields an in-group action);
            # all-healthy divides by exactly 1.0 — the pre-fault argmin
            ct = jnp.maximum(trow.arrival, state.avail) \
                + sp.exec_time[:, trow.kind] / health_capacity(state)
            ct = jnp.where(plan.group_mask[s], ct, jnp.inf)
            return jnp.argmin(ct).astype(jnp.int32)
    elif policy == "flexai":
        def act(params, sp, state, trow, s):
            sv = stage_state_vector(
                spec, feat, backlog_scale, state, trow,
                stage_exec=sp.exec_time,
                mac_frac=plan.mac_frac[s, trow.kind],
                group_mask=plan.group_mask[s],
                stage_frac=s.astype(jnp.float32) if hasattr(s, "astype")
                else jnp.float32(s))
            # mask to live group members; if the whole group is down fall
            # back to the bare group mask (least-bad in-group placement)
            gmask = plan.group_mask[s] & state.alive
            gmask = jnp.where(gmask.any(), gmask, plan.group_mask[s])
            q = jnp.where(gmask, qnet_apply(params, sv), -jnp.inf)
            return jnp.argmax(q).astype(jnp.int32)
    elif policy == "task":
        def act(params, sp, state, trow, s):
            sv = state_vector(spec, feat, backlog_scale, state, trow)
            amask = jnp.where(state.alive.any(), state.alive,
                              jnp.ones_like(state.alive))
            q = jnp.where(amask, qnet_apply(params, sv), -jnp.inf)
            return jnp.argmax(q).astype(jnp.int32)
    else:
        raise ValueError(f"unknown pipeline policy {policy!r}")
    return act


def _stage_obs(spec, plan, feat, backlog_scale, state, ring, row, s):
    """(stage sub-task view, stage observation) for the training paths."""
    S = plan.stage_exec.shape[0]
    trow = _stage_task_view(plan, ring, row, s)
    sv = stage_state_vector(
        spec, feat, backlog_scale, state, trow,
        stage_exec=plan.stage_exec[s],
        mac_frac=plan.mac_frac[s, row.kind],
        group_mask=plan.group_mask[s],
        stage_frac=s.astype(jnp.float32) / S)
    return trow, sv


# ---------------------------------------------------------------------------
# wavefront stream layout
# ---------------------------------------------------------------------------

def _wavefront_stream(tasks: TaskArrays, S: int):
    """Flatten a [T]-task route into the [(T+S-1)*S] wavefront stream.

    Column c holds steps (k = c - s, s); within a column stages run
    DESCENDING so stage s reads ring[s-1] (written at column c-1) before
    stage s-1 overwrites it — the single-device serialization of the
    per-column parallel wavefront.  Out-of-range corners become invalid
    rows (clip-gathered, state passthrough).
    """
    T = tasks.arrival.shape[0]
    C = T + S - 1
    s_seq = jnp.tile(jnp.arange(S - 1, -1, -1), C)
    k_seq = jnp.repeat(jnp.arange(C), S) - s_seq
    ok = (k_seq >= 0) & (k_seq < T)
    rows = jax.tree_util.tree_map(
        lambda a: a[jnp.clip(k_seq, 0, T - 1)], tasks)
    return rows._replace(valid=rows.valid & ok), s_seq


def _record_order(T: int, S: int) -> jax.Array:
    """[T, S] gather indices mapping the flat wavefront record stream back
    to task-major ``recs[k, s]`` (step (k, s) ran at flat position
    ``(k+s)*S + (S-1-s)``)."""
    k = jnp.arange(T)[:, None]
    s = jnp.arange(S)[None, :]
    return (k + s) * S + (S - 1 - s)


# ---------------------------------------------------------------------------
# inference engines
# ---------------------------------------------------------------------------

def _pipeline_segment_run(spec: PlatformSpec, plan: StagePlan,
                          backlog_scale: float = 1.0,
                          policy: str = "flexai"):
    """Un-jitted runner over a PRE-FLATTENED wavefront segment: the
    serving seam.  ``run(params, rows, s_seq, state0, ring0) -> (state,
    ring, recs)`` — QoS waves slice the flat stream into micro-batch
    segments and checkpoint ``(state, ring)`` at the (stage-boundary)
    segment cuts."""
    act = _make_policy(policy, spec, plan, backlog_scale)
    S = int(plan.stage_exec.shape[0])

    def body(params, carry, x):
        state, ring = carry
        row, s, hrow = x
        # health rows are indexed by TASK: every stage of task k installs
        # row k before acting, so the wavefront interleaving and the
        # task-major reference agree step-for-step under the same trace
        state = with_health(state, hrow)
        sp = stage_spec(spec, plan, s)
        trow = _stage_task_view(plan, ring, row, s)
        action = act(params, sp, state, trow, s)
        state2, rec = platform_step(sp, state, trow, action)
        ring2 = ring.at[s].set(jnp.where(row.valid, rec.finish, ring[s]))
        return (state2, ring2), rec

    def run(params, rows, s_seq, state0=None, ring0=None, health=None):
        init = platform_init(spec.n) if state0 is None else state0
        ring = jnp.zeros((S,), jnp.float32) if ring0 is None else ring0
        trace = (jnp.ones((rows.arrival.shape[0], spec.n), jnp.float32)
                 if health is None else jnp.asarray(health, jnp.float32))
        (final, ringf), recs = jax.lax.scan(
            functools.partial(body, params), (init, ring),
            (rows, s_seq, trace))
        return final, ringf, recs

    return run


def _pipeline_run(spec: PlatformSpec, plan: StagePlan,
                  backlog_scale: float = 1.0, policy: str = "flexai"):
    """Un-jitted full-route wavefront episode: flatten, scan, regather.
    ``run(params, tasks) -> (final_state, ring, recs[T, S])``."""
    seg = _pipeline_segment_run(spec, plan, backlog_scale, policy)
    S = int(plan.stage_exec.shape[0])

    def run(params, tasks: TaskArrays, state0=None, ring0=None,
            health=None):
        T = tasks.arrival.shape[0]
        rows, s_seq = _wavefront_stream(tasks, S)
        hflat = None
        if health is not None:
            # [T, n] task-indexed trace -> flat wavefront order (the
            # clip-gather mirrors _wavefront_stream; corner rows are
            # overwritten before any later action, so clipping is safe)
            k_seq = jnp.repeat(jnp.arange(T + S - 1), S) \
                - jnp.tile(jnp.arange(S - 1, -1, -1), T + S - 1)
            hflat = jnp.asarray(health, jnp.float32)[
                jnp.clip(k_seq, 0, T - 1)]
        final, ring, recs = seg(params, rows, s_seq, state0, ring0,
                                health=hflat)
        recs = jax.tree_util.tree_map(
            lambda a: a[_record_order(T, S)], recs)
        return final, ring, recs

    return run


def make_pipeline_schedule_fn(spec: PlatformSpec, plan: StagePlan,
                              backlog_scale: float = 1.0,
                              policy: str = "flexai",
                              batched: bool = False):
    """Compile the flattened wavefront scheduler; ``batched=True`` vmaps a
    [R, T] route batch (params shared)."""
    run = _pipeline_run(spec, plan, backlog_scale, policy)
    if batched:
        single = run

        def run(params, tasks, health=None):
            if health is None:
                return jax.vmap(single, in_axes=(None, 0))(params, tasks)
            return jax.vmap(lambda p, t, h: single(p, t, health=h),
                            in_axes=(None, 0, 0))(params, tasks, health)
    return jax.jit(run)


def _pipeline_reference_run(spec: PlatformSpec, plan: StagePlan,
                            backlog_scale: float = 1.0,
                            policy: str = "flexai"):
    """Unpipelined task-major reference: every task runs all S stages to
    completion before the next task starts (stages unrolled in the scan
    body).  Per-group commit sequences are identical to the wavefront's,
    so final states and records match the pipelined engines bit-exactly —
    the parity oracle of the ISSUE-7 contract."""
    act = _make_policy(policy, spec, plan, backlog_scale)
    S = int(plan.stage_exec.shape[0])

    def body(params, carry, x):
        row, hrow = x
        state, ring = carry
        state = with_health(state, hrow)
        out = []
        for s_i in range(S):
            s = jnp.int32(s_i)
            sp = stage_spec(spec, plan, s)
            trow = _stage_task_view(plan, ring, row, s)
            action = act(params, sp, state, trow, s)
            state, rec = platform_step(sp, state, trow, action)
            ring = ring.at[s_i].set(
                jnp.where(row.valid, rec.finish, ring[s_i]))
            out.append(rec)
        recs = jax.tree_util.tree_map(lambda *r: jnp.stack(r), *out)
        return (state, ring), recs

    def run(params, tasks: TaskArrays, health=None):
        t = tasks.arrival.shape[0]
        trace = (jnp.ones((t, spec.n), jnp.float32) if health is None
                 else jnp.asarray(health, jnp.float32))
        init = (platform_init(spec.n), jnp.zeros((S,), jnp.float32))
        (final, ring), recs = jax.lax.scan(
            functools.partial(body, params), init, (tasks, trace))
        return final, ring, recs

    return run


def make_pipeline_reference_fn(spec: PlatformSpec, plan: StagePlan,
                               backlog_scale: float = 1.0,
                               policy: str = "flexai",
                               batched: bool = False):
    run = _pipeline_reference_run(spec, plan, backlog_scale, policy)
    if batched:
        single = run

        def run(params, tasks, health=None):
            if health is None:
                return jax.vmap(single, in_axes=(None, 0))(params, tasks)
            return jax.vmap(lambda p, t, h: single(p, t, health=h),
                            in_axes=(None, 0, 0))(params, tasks, health)
    return jax.jit(run)


def make_sharded_pipeline_fn(spec: PlatformSpec, plan: StagePlan, mesh,
                             backlog_scale: float = 1.0,
                             policy: str = "flexai",
                             stage_axis: str = "stages",
                             route_axis: str = "routes"):
    """Compile the stage-sharded wavefront over a 2-D ``(stages, routes)``
    mesh: each stage group runs on its own device shard, scanning
    wavefront columns over its local routes, and the finish ring hops
    stage s -> s+1 through ``lax.ppermute`` after every column — the
    cross-mesh resharding collective (the payload whose latency
    ``plan.reshard_s`` charges to the downstream arrival).

    ``fn(params, tasks[R, T]) -> (states [S, R, ...], ring [S, R],
    recs [S, R, T])`` where ``recs[s, r, k]`` equals the flattened
    engine's ``recs[r][k, s]`` bit-exactly and
    :func:`combine_stage_states` folds the per-shard states back into the
    global platform state.  R must be a multiple of the route-axis size
    (``tasks.pad_route_batch``).
    """
    from jax.sharding import PartitionSpec as P

    S = int(plan.stage_exec.shape[0])
    if mesh.shape[stage_axis] != S:
        raise ValueError(
            f"mesh axis {stage_axis!r} has size {mesh.shape[stage_axis]}, "
            f"plan has {S} stages")
    act = _make_policy(policy, spec, plan, backlog_scale)

    def block(params, tasks: TaskArrays):
        my_s = jax.lax.axis_index(stage_axis)
        R, T = tasks.arrival.shape
        C = T + S - 1
        sp = stage_spec(spec, plan, my_s)

        def col(carry, c):
            states, ring, recv = carry
            k = c - my_s
            okc = (k >= 0) & (k < T)
            rows = jax.tree_util.tree_map(
                lambda a: a[:, jnp.clip(k, 0, T - 1)], tasks)
            rows = rows._replace(valid=rows.valid & okc)

            def one(state, row, rv):
                prev = jnp.maximum(my_s - 1, 0)
                arrival = jnp.where(
                    jnp.equal(my_s, 0), row.arrival,
                    rv + plan.reshard_s[prev, row.kind])
                trow = row._replace(
                    arrival=arrival,
                    safety=row.safety - (arrival - row.arrival))
                action = act(params, sp, state, trow, my_s)
                return platform_step(sp, state, trow, action)

            states2, recs = jax.vmap(one)(states, rows, recv)
            ring2 = jnp.where(rows.valid, recs.finish, ring)
            if S > 1:
                nxt = jax.lax.ppermute(
                    ring2, stage_axis, [(i, i + 1) for i in range(S - 1)])
            else:
                nxt = recv
            return (states2, ring2, nxt), recs

        states0 = jax.vmap(lambda _: platform_init(spec.n))(jnp.arange(R))
        z = jnp.zeros((R,), jnp.float32)
        # each stage shard's carry varies over both mesh axes
        (statesF, ringF, _), recs = jax.lax.scan(
            col, vary_like((states0, z, z), tasks.arrival, my_s),
            jnp.arange(C))
        recs = jax.tree_util.tree_map(
            lambda a: jnp.moveaxis(a, 0, 1), recs)          # [R, C]
        cols = my_s + jnp.arange(T)                          # own diagonal
        recs = jax.tree_util.tree_map(lambda a: a[:, cols], recs)
        lead = lambda a: a[None]  # noqa: E731
        return (jax.tree_util.tree_map(lead, statesF), ringF[None],
                jax.tree_util.tree_map(lead, recs))

    sharded = jax.shard_map(
        block, mesh=mesh, in_specs=(P(), P(route_axis)),
        out_specs=(P(stage_axis, route_axis), P(stage_axis, route_axis),
                   P(stage_axis, route_axis)))
    return jax.jit(sharded)


def combine_stage_states(plan: StagePlan, states: PlatformState
                         ) -> PlatformState:
    """Fold per-stage-shard states ([S, ...] leading axis, optional route
    axis next) into the global platform state: accelerator i's row comes
    from its own group's shard, and the running scales are recomputed —
    they equal the flattened engine's finals because both are running
    maxima of monotone totals."""
    idx = jnp.arange(plan.groups.shape[0])

    def pick(a):
        b = jnp.moveaxis(a, 0, -1)                   # [..., n, S]
        return b[..., idx, plan.groups]

    E, T = pick(states.E), pick(states.T)
    return PlatformState(
        avail=pick(states.avail), busy=pick(states.busy), E=E, T=T,
        MS=pick(states.MS), R_Balance=pick(states.R_Balance),
        num_tasks=pick(states.num_tasks),
        e_scale=jnp.maximum(jnp.float32(1e-9), E.sum(-1)),
        t_scale=jnp.maximum(jnp.float32(1e-9), T.max(-1)),
        alive=pick(states.alive), cap=pick(states.cap))


def pipeline_summarize(spec: PlatformSpec, state: PlatformState,
                       recs) -> dict:
    """Route summary from [.., T, S] stage records: end-to-end verdicts
    (met/response/wait) come from the FINAL stage, whose safety budget
    already absorbed every upstream delay."""
    last = jax.tree_util.tree_map(lambda a: a[..., -1], recs)
    summ = summarize(spec, state, last)
    summ["stages"] = int(recs.valid.shape[-1])
    return summ


# ---------------------------------------------------------------------------
# stage-level FlexAI training
# ---------------------------------------------------------------------------

def _next_valid_flat(valid: jax.Array):
    """Per flat step i: index of the next valid step (> i), self + done
    when none remains — the wavefront analogue of ``_train_run``'s
    next-task pairing.  State/ring never change across the skipped invalid
    corners, so bootstrapping with the CURRENT post-step state is exact.
    ``valid`` may carry leading batch axes; the scan runs on the last."""
    L = valid.shape[-1]
    ar = jnp.arange(L)
    pos = jnp.where(valid, ar, L)
    suff = jax.lax.associative_scan(jnp.minimum, pos, reverse=True,
                                    axis=pos.ndim - 1)
    nv = jnp.concatenate(
        [suff[..., 1:], jnp.full(valid.shape[:-1] + (1,), L, suff.dtype)],
        axis=-1)
    done = valid & (nv >= L)
    return jnp.where(nv >= L, ar, nv), done


def _pipeline_train_run(spec: PlatformSpec, plan: StagePlan, cfg):
    """Single-lane fused stage-placement training episode: ``_train_run``
    on the flattened wavefront stream.  Exploration samples uniformly
    WITHIN the stage group (a stage action outside its group is not in
    the action support), greedy is the group-masked Q argmax."""
    feat = jnp.asarray(kind_feature_table())
    n_actions = spec.n
    S = int(plan.stage_exec.shape[0])

    def body(carry, x):
        ts, plat, ring, sv = carry
        row, s, nrow, ns, done = x
        key, k_eps, k_act, k_smp = jax.random.split(ts.key, 4)

        frac = jnp.minimum(
            1.0, ts.env_steps.astype(jnp.float32)
            / max(cfg.eps_decay_steps, 1))
        eps = cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac
        maskf = plan.group_mask[s].astype(jnp.float32)
        explore = jax.random.uniform(k_eps) < eps
        greedy = jnp.argmax(jnp.where(plan.group_mask[s],
                                      qnet_apply(ts.eval_p, sv), -jnp.inf))
        rand = jax.random.choice(k_act, n_actions, p=maskf / maskf.sum())
        action = jnp.where(explore, rand, greedy).astype(jnp.int32)

        sp = stage_spec(spec, plan, s)
        trow = _stage_task_view(plan, ring, row, s)
        plat2, rec = platform_step(sp, plat, trow, action)
        ring2 = ring.at[s].set(jnp.where(row.valid, rec.finish, ring[s]))
        reward = reward_from_states(spec, plat, plat2)
        _, nsv = _stage_obs(spec, plan, feat, cfg.backlog_scale,
                            plat2, ring2, nrow, ns)

        valid = row.valid
        replay = device_replay_add(ts.replay, sv, action, reward, nsv,
                                   done.astype(jnp.float32), write=valid)
        env_steps = ts.env_steps + valid.astype(jnp.int32)
        do_update = (valid & (replay.size >= cfg.min_replay)
                     & (env_steps % cfg.update_every == 0))

        def upd(_):
            batch = device_replay_sample(replay, k_smp, cfg.batch_size)
            new_p, new_opt, loss = dqn_td_update(
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
        return (ts2, plat2, ring2, nsv), (rec, loss, do_update)

    def run(ts: TrainState, tasks: TaskArrays):
        T = tasks.arrival.shape[0]
        rows, s_seq = _wavefront_stream(tasks, S)
        nv, done = _next_valid_flat(rows.valid)
        nrows = jax.tree_util.tree_map(lambda a: a[nv], rows)
        ns = s_seq[nv]
        plat0, ring0 = vary_like(
            (platform_init(spec.n), jnp.zeros((S,), jnp.float32)),
            tasks.arrival)
        _, sv0 = _stage_obs(
            spec, plan, feat, cfg.backlog_scale, plat0, ring0,
            jax.tree_util.tree_map(lambda a: a[0], rows), s_seq[0])
        (ts_f, plat_f, _, _), (recs, losses, upd) = jax.lax.scan(
            body, (ts, plat0, ring0, sv0), (rows, s_seq, nrows, ns, done))
        recs = jax.tree_util.tree_map(
            lambda a: a[_record_order(T, S)], recs)
        return ts_f, plat_f, recs, losses, upd

    return run


def make_pipeline_train_fn(spec: PlatformSpec, plan: StagePlan, cfg,
                           batched: bool = False):
    """Compile the fused stage-placement trainer; ``batched=True`` vmaps
    independent population lanes (stacked TrainState x stacked routes)."""
    run = _pipeline_train_run(spec, plan, cfg)
    if batched:
        run = jax.vmap(run, in_axes=(0, 0))
    return jax.jit(run)


def make_sharded_pipeline_train_fn(spec: PlatformSpec, plan: StagePlan,
                                   cfg, mesh, axis: str = "routes"):
    """Population training sharded over ``axis``: independent per-lane
    stage agents, no collectives (the pipeline analogue of
    ``make_sharded_train_fn``)."""
    from jax.sharding import PartitionSpec as P

    run = jax.vmap(_pipeline_train_run(spec, plan, cfg), in_axes=(0, 0))
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(P(axis), P(axis)),
                            out_specs=P(axis))
    return jax.jit(sharded)


def _pipeline_dp_train_run(spec: PlatformSpec, plan: StagePlan, cfg,
                           lanes: int, axis=None, n_shards: int = 1):
    """Data-parallel stage-placement training: ONE synchronized agent over
    ``lanes`` local route lanes (x ``n_shards`` devices), the pipeline
    analogue of ``_dp_train_run`` — with the chunked-collective layout:
    a tiny per-step stats psum gates the update, and the gradient
    all-reduce + Adam step run inside ``lax.cond`` only on optimizer
    steps (the predicate is shard-uniform by construction, so every shard
    takes the same branch and the conditional collective cannot
    deadlock)."""
    feat = jnp.asarray(kind_feature_table())
    n_actions = spec.n
    S = int(plan.stage_exec.shape[0])

    if axis is None:
        psum = pmean = lambda x: x
        n_shards = 1
    else:
        psum = functools.partial(jax.lax.psum, axis_name=axis)
        pmean = functools.partial(jax.lax.pmean, axis_name=axis)

    def body(gidx, carry, x):
        ts, plats, rings, svs = carry
        row, s, nrow, ns, done = x          # row leaves [lanes]; s scalar
        key, k_eps, k_act, k_smp = jax.random.split(ts.key, 4)

        def lane_keys(k):
            ks = jax.vmap(lambda g: jax.random.fold_in(k, g))(gidx)
            return jnp.where((gidx == 0)[:, None], k[None, :], ks)

        frac = jnp.minimum(
            1.0, ts.env_steps.astype(jnp.float32)
            / max(cfg.eps_decay_steps, 1))
        eps = cfg.eps_start + (cfg.eps_end - cfg.eps_start) * frac
        sp = stage_spec(spec, plan, s)
        maskf = plan.group_mask[s].astype(jnp.float32)

        def act_step(plat, ring, sv, row_l, nrow_l, ns_l, ke, ka):
            explore = jax.random.uniform(ke) < eps
            greedy = jnp.argmax(jnp.where(
                plan.group_mask[s], qnet_apply(ts.eval_p, sv), -jnp.inf))
            rand = jax.random.choice(ka, n_actions, p=maskf / maskf.sum())
            action = jnp.where(explore, rand, greedy).astype(jnp.int32)
            trow = _stage_task_view(plan, ring, row_l, s)
            plat2, rec = platform_step(sp, plat, trow, action)
            ring2 = ring.at[s].set(
                jnp.where(row_l.valid, rec.finish, ring[s]))
            reward = reward_from_states(spec, plat, plat2)
            _, nsv = _stage_obs(spec, plan, feat, cfg.backlog_scale,
                                plat2, ring2, nrow_l, ns_l)
            return plat2, ring2, rec, action, reward, nsv

        plats2, rings2, recs, actions, rewards, nsvs = jax.vmap(act_step)(
            plats, rings, svs, row, nrow, ns,
            lane_keys(k_eps), lane_keys(k_act))
        replay = jax.vmap(device_replay_add)(
            ts.replay, svs, actions, rewards, nsvs,
            done.astype(jnp.float32), row.valid)

        # chunked collectives: only the 2-float gate stats all-reduce
        # every step; the gradient all-reduce waits for an optimizer step
        stats = psum(jnp.stack([
            row.valid.astype(jnp.float32).sum(),
            (replay.size.min() >= cfg.min_replay).astype(jnp.float32)]))
        env_steps = ts.env_steps + stats[0].astype(jnp.int32)
        crossed = (env_steps // cfg.update_every
                   > ts.env_steps // cfg.update_every)
        do_update = crossed & (stats[1] == float(n_shards))

        def upd(_):
            batches = jax.vmap(
                lambda b, k: device_replay_sample(b, k, cfg.batch_size)
            )(replay, lane_keys(k_smp))
            # shard-local view of the shared weights (see _dp_train_run)
            eval_p, targ_p = vary_like((ts.eval_p, ts.targ_p), replay.size)
            losses, grads = jax.vmap(
                lambda b: dqn_td_grads(eval_p, targ_p, b,
                                       gamma=cfg.gamma))(batches)
            flat, unravel = jax.flatten_util.ravel_pytree(
                (losses.mean(),
                 jax.tree_util.tree_map(lambda g: g.mean(0), grads)))
            loss, g = unravel(pmean(flat))
            new_p, new_opt = adam_apply(ts.eval_p, ts.opt, g, lr=cfg.lr)
            return new_p, new_opt, loss

        def skip(_):
            return ts.eval_p, ts.opt, jnp.float32(0.0)

        eval_p, opt, loss = jax.lax.cond(do_update, upd, skip, None)
        updates = ts.updates + do_update.astype(jnp.int32)
        sync = do_update & (updates % cfg.target_sync_every == 0)
        targ_p = jax.tree_util.tree_map(
            lambda e, t: jnp.where(sync, e, t), eval_p, ts.targ_p)
        ts2 = TrainState(eval_p=eval_p, targ_p=targ_p, opt=opt,
                         replay=replay, env_steps=env_steps,
                         updates=updates, key=key)
        return (ts2, plats2, rings2, nsvs), (recs, loss, do_update)

    def run(ts: TrainState, tasks: TaskArrays):
        base = 0 if axis is None else jax.lax.axis_index(axis) * lanes
        gidx = base + jnp.arange(lanes)
        T = tasks.arrival.shape[1]
        C = T + S - 1
        L = C * S
        s_seq = jnp.tile(jnp.arange(S - 1, -1, -1), C)
        k_seq = jnp.repeat(jnp.arange(C), S) - s_seq
        ok = (k_seq >= 0) & (k_seq < T)
        rows = jax.tree_util.tree_map(
            lambda a: a[:, jnp.clip(k_seq, 0, T - 1)], tasks)
        rows = rows._replace(valid=rows.valid & ok[None, :])
        nv, done = _next_valid_flat(rows.valid)       # [lanes, L] each
        nrows = jax.tree_util.tree_map(
            lambda a: jnp.take_along_axis(a, nv, axis=1), rows)
        ns = s_seq[nv]
        plats0, rings0 = vary_like(
            (jax.vmap(lambda _: platform_init(spec.n))(jnp.arange(lanes)),
             jnp.zeros((lanes, S), jnp.float32)), tasks.arrival)
        svs0 = jax.vmap(
            lambda p, r, rw: _stage_obs(spec, plan, feat, cfg.backlog_scale,
                                        p, r, rw, s_seq[0])[1]
        )(plats0, rings0, jax.tree_util.tree_map(lambda a: a[:, 0], rows))
        swap = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
        xs = (jax.tree_util.tree_map(swap, rows), s_seq,
              jax.tree_util.tree_map(swap, nrows), swap(ns), swap(done))
        (ts_f, plats_f, _, _), (recs, losses, upd) = jax.lax.scan(
            functools.partial(body, gidx), (ts, plats0, rings0, svs0), xs)
        recs = jax.tree_util.tree_map(
            lambda a: swap(a)[:, _record_order(T, S)], recs)
        return ts_f, plats_f, recs, losses, upd

    return run


def make_pipeline_dp_train_fn(spec: PlatformSpec, plan: StagePlan, cfg,
                              lanes: int, mesh=None,
                              axis: str = "routes"):
    """Compile the data-parallel stage trainer (contract mirrors
    ``make_dp_train_fn``: [lanes, T] route batch, shared agent, per-lane
    replay; with ``mesh`` the lane axis shards over ``axis``)."""
    if mesh is None:
        return jax.jit(_pipeline_dp_train_run(spec, plan, cfg, lanes))
    from jax.sharding import PartitionSpec as P

    if lanes < 1 or lanes % mesh.size:
        raise ValueError(f"lanes={lanes} must be a positive multiple of "
                         f"the mesh size {mesh.size}")
    run = _pipeline_dp_train_run(spec, plan, cfg, lanes // mesh.size,
                                 axis=axis, n_shards=mesh.size)
    ts_specs = TrainState(eval_p=P(), targ_p=P(), opt=P(), replay=P(axis),
                          env_steps=P(), updates=P(), key=P())
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(ts_specs, P(axis)),
                            out_specs=(ts_specs, P(axis), P(axis), P(), P()))
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# host-side wrapper
# ---------------------------------------------------------------------------

class PipelineFlexAI:
    """Stage-placement FlexAI on the pipeline wavefront engines:
    ``ScanFlexAI``'s train/schedule surface where the action places a
    *stage* onto its accelerator group.

    Modes mirror ``ScanFlexAI``: single lane (default), ``lanes > 1``
    population agents (optionally sharded over ``mesh``), or ``dp=True``
    for one synchronized agent trained data-parallel over a lane batch.
    """

    def __init__(self, platform, cfg, n_stages: int = 2, lanes: int = 1,
                 mesh=None, dp: bool = False, plan: StagePlan = None):
        self.cfg = cfg
        self.spec = spec_from_platform(platform)
        self.plan = plan if plan is not None \
            else build_stage_plan(platform, n_stages)
        self.n_stages = int(self.plan.stage_exec.shape[0])
        self.n_actions = platform.n
        self.state_dim = stage_state_dim(platform.n)
        self.lanes = lanes
        self.mesh = mesh
        self.dp = dp
        key = jax.random.PRNGKey(cfg.seed)
        if dp:
            self.ts = dp_train_init(key, self.state_dim, self.n_actions,
                                    cfg.replay_capacity, lanes)
            self._train_fn = make_pipeline_dp_train_fn(
                self.spec, self.plan, cfg, lanes, mesh=mesh,
                axis=mesh.axis_names[-1] if mesh is not None else "routes")
        elif lanes == 1:
            self.ts = train_init(key, self.state_dim, self.n_actions,
                                 cfg.replay_capacity)
            self._train_fn = make_pipeline_train_fn(self.spec, self.plan,
                                                    cfg)
        else:
            self.ts = jax.vmap(
                lambda k: train_init(k, self.state_dim, self.n_actions,
                                     cfg.replay_capacity)
            )(jax.random.split(key, lanes))
            if mesh is not None:
                if lanes < 2 or lanes % mesh.size:
                    raise ValueError(
                        f"lanes={lanes} must be >= 2 and a multiple of "
                        f"the mesh size {mesh.size}")
                self._train_fn = make_sharded_pipeline_train_fn(
                    self.spec, self.plan, cfg, mesh,
                    axis=mesh.axis_names[-1])
            else:
                self._train_fn = make_pipeline_train_fn(
                    self.spec, self.plan, cfg, batched=True)
        self._sched_fn = make_pipeline_schedule_fn(
            self.spec, self.plan, cfg.backlog_scale)
        self._eval_fn = None
        self.losses: list = []
        self.best_eval_stm = None
        self._best_stm: float = -1.0
        self._best_params = None

    def _as_arrays(self, tasks) -> TaskArrays:
        return tasks if isinstance(tasks, TaskArrays) else \
            tasks_to_arrays(tasks)

    def train_episode(self, tasks) -> dict:
        if self.lanes > 1 or self.dp:
            ta = tasks if isinstance(tasks, TaskArrays) else \
                stack_task_arrays([self._as_arrays(q) for q in tasks])
            if self.dp and ta.arrival.ndim == 1:
                ta = TaskArrays(*[np.asarray(f)[None] for f in ta])
        else:
            ta = self._as_arrays(tasks)
        self.ts, plat, recs, losses, upd = self._train_fn(self.ts, ta)
        losses, upd = np.asarray(losses), np.asarray(upd, bool)
        if upd.any():
            self.losses.extend(losses[upd].tolist())
        lanes_out = 1 if (self.lanes == 1 and not self.dp) else self.lanes
        if lanes_out == 1 and not self.dp:
            s = pipeline_summarize(self.spec, plat, recs)
            s["mean_loss"] = float(losses[upd].mean()) if upd.any() else None
            return s
        summ = []
        for i in range(lanes_out):
            lane = pipeline_summarize(
                self.spec,
                jax.tree_util.tree_map(lambda a, i=i: a[i], plat),
                jax.tree_util.tree_map(lambda a, i=i: a[i], recs))
            if not self.dp:
                m = upd[i]
                lane["mean_loss"] = (float(losses[i][m].mean())
                                     if m.any() else None)
            summ.append(lane)
        if self.dp:
            mean_loss = float(losses[upd].mean()) if upd.any() else None
            if lanes_out == 1:
                summ[0]["mean_loss"] = mean_loss
                return summ[0]
            return {"lanes": summ, "mean_loss": mean_loss}
        return {"lanes": summ}

    def train(self, queues: list, episodes: int, eval_queue=None,
              eval_every: int = 5) -> list:
        """Cycle the queue pool with ``ScanFlexAI.train``'s cadence and
        model selection (best-eval EvalNet restored at the end)."""
        routes = [self._as_arrays(q) for q in queues]
        if self.lanes > 1 or self.dp:
            t_max = max(r.arrival.shape[-1] for r in routes)
            routes = [pad_task_arrays(r, t_max)
                      if r.arrival.shape[-1] < t_max else r for r in routes]
        ta_eval = self._as_arrays(eval_queue) \
            if eval_queue is not None else None
        history = []
        self._best_stm, self._best_params = -1.0, None
        per_lane = 1 if (self.lanes == 1 and not self.dp) else self.lanes
        for ep in range(episodes):
            if per_lane == 1:
                history.append(self.train_episode(routes[ep % len(routes)]))
            else:
                history.append(self.train_episode(
                    [routes[(ep * per_lane + i) % len(routes)]
                     for i in range(per_lane)]))
            if ta_eval is not None and (ep + 1) % eval_every == 0:
                stms = self._eval_stms(ta_eval)
                history[-1]["eval_stm"] = stms[0] if len(stms) == 1 else stms
                lane = int(np.argmax(stms))
                if stms[lane] > self._best_stm:
                    self._best_stm = stms[lane]
                    self._best_params = self.eval_params(lane)
        if self._best_params is not None:
            self.set_params(self._best_params)
            self.best_eval_stm = self._best_stm
        return history

    def _eval_stms(self, ta_eval: TaskArrays) -> list:
        if self.dp or self.lanes == 1:
            final, _, recs = self._sched_fn(self.eval_params(), ta_eval)
            return [pipeline_summarize(self.spec, final, recs)["stm_rate"]]
        if self._eval_fn is None:
            self._eval_fn = jax.jit(jax.vmap(
                _pipeline_run(self.spec, self.plan, self.cfg.backlog_scale),
                in_axes=(0, None)))
        finals, _, recs = self._eval_fn(self.ts.eval_p, ta_eval)
        return [pipeline_summarize(
            self.spec,
            jax.tree_util.tree_map(lambda a, i=i: a[i], finals),
            jax.tree_util.tree_map(lambda a, i=i: a[i], recs))["stm_rate"]
            for i in range(self.lanes)]

    def eval_params(self, lane: int = 0) -> DQNParams:
        if self.dp or self.lanes == 1:
            return self.ts.eval_p
        return jax.tree_util.tree_map(lambda a: a[lane], self.ts.eval_p)

    def set_params(self, params: DQNParams) -> None:
        if self.dp or self.lanes == 1:
            eval_p = params
        else:
            eval_p = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(
                    a, (self.lanes,) + a.shape).copy(), params)
        self.ts = self.ts._replace(
            eval_p=eval_p, targ_p=eval_p,
            opt=jax.tree_util.tree_map(jnp.zeros_like, self.ts.opt))

    def save_weights(self, path: str, lane: int = 0) -> None:
        from repro.core.flexai.dqn import save_dqn_npz
        save_dqn_npz(path, self.eval_params(lane))

    def load_weights(self, path: str) -> None:
        from repro.core.flexai.dqn import load_dqn_npz
        self.set_params(load_dqn_npz(path))

    def schedule(self, tasks, lane: int = 0) -> dict:
        ta = self._as_arrays(tasks)
        t0 = time.perf_counter()
        final, _, recs = self._sched_fn(self.eval_params(lane), ta)
        jax.block_until_ready(final)
        dt = time.perf_counter() - t0
        summ = pipeline_summarize(self.spec, final, recs)
        summ["schedule_time_s"] = dt
        summ["schedule_time_per_task_s"] = dt / max(ta.num_tasks, 1)
        summ["placements"] = np.asarray(recs.action)   # [T, S]
        return summ
