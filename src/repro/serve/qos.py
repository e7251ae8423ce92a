"""Deadline-aware QoS serving for FlexAI placement requests (ISSUE 5).

The paper's headline serving claim — "basically 100% of tasks in each
driving route are processed within their required period" — is a *deadline*
guarantee, not a throughput one.  This module adds the deadline story the
wave-based serving layer was missing:

* every request carries an absolute deadline derived from the Table-5
  period requirements (``tasks.route_deadline_budget``);
* admission is EDF-within-bucket with a cross-bucket **aging credit**, so
  a long-route bucket cannot be starved by a stream of tight short routes
  (each wave a queued request is passed over lowers its effective deadline
  by ``aging_credit``; after ``spread/credit + n_queued`` waves it beats
  any newcomer — the bound ``tests/test_serve_properties.py`` checks);
* a running wave is **preemptible**: between service segments it
  checkpoints its batched ``PlatformState`` (the same pytree
  ``state_from_platform`` snapshots) and yields when a sufficiently
  tighter-deadline request is waiting (laxity rule below); the checkpoint
  resumes through the scan engine's ``state0=`` seam, bit-exactly;
* queued requests whose deadline can no longer be met are **shed** to a
  dead-letter log instead of burning wave slots on doomed work.

Time is a *virtual clock*: serving work is charged at ``svc_per_task``
seconds per lockstep task slot (padding included — the static-shape wave
pays for its padding, exactly like the real engine).  That keeps every
admission decision, preemption point and miss/shed verdict deterministic,
which is what the property suite and the CI gate need; wall-clock serving
latency rides on top without changing any decision.  With
``cfg.measured_svc`` the clock is instead advanced by *measured* segment
wall time and a per-(bucket, stages) EMA of it replaces the constant in
shedding/preemption decisions, so admission tracks the hardware the pool
actually has (virtual stays the deterministic fallback — see DESIGN.md).

Placements are real: each wave dispatches through the vmapped greedy scan
engine (``flexai.engine._schedule_run`` with ``state0`` resume), so
``stm_rate`` at the serving boundary is measured on actual schedules, not
a queueing abstraction.  A ``stub`` executor swaps the device dispatch for
a state pass-through when only the queueing discipline is under test.

With ``cfg.stages > 1`` a wave serves *pipeline* placements
(``core.pipeline``): each lane's route is flattened into the wavefront
stream at admission, service segments are micro-batches of flat
(task, stage) steps, and the preemption checkpoint widens to ``(state,
ring)`` — the ring of per-stage upstream finish times is exactly what a
resumed wave needs to keep charging cross-stage handoffs.  The virtual
clock charges ``svc/stages`` per flat slot, so a pipelined wave costs
the same service time as its unpipelined twin up to the (S-1)-column
drain bubble.  Params must come from a stage-level agent
(``PipelineFlexAI``); the durability layer does not support pipeline
waves (gated off in ``launch/serve.py`` and ``DurableQoSEngine``).

Two production paths land on top (ISSUE 10):

* **Sharded waves** (``mesh=``): the wave's lane axis is shard_mapped
  over the ``("routes",)`` mesh, lanes padded to the mesh size with
  invalid rows + fresh states and trimmed back — per-lane scans are
  independent, so placements are bit-exact vs the single-device path
  (the parity trace in ``benchmarks/serve_load.py`` pins it).

* **Continuous batching** (``cfg.continuous``): instead of draining a
  wave before re-admitting, a freed lane (completed — or shed mid-flight
  once its remaining service can no longer meet its deadline) is
  refilled at the next segment boundary from the backlog, JetStream
  prefill-insert style.  Refill only admits the request global admission
  would pick next (and only if its bucket matches the in-flight wave),
  so EDF ordering and the aging starvation bound survive; the refilled
  lane's ``PlatformState`` row is reinitialized, and the wave remains a
  preemptible checkpointed unit with per-lane cursors.

Tracing: ``engine.tracer = serve.tracing.Tracer()`` records the loop's
spans — ``admit`` (children ``admit.pack_tasks``, ``admit.init_state``),
``segment`` (``segment.slice``, ``segment.call``, ``hook`` around
``_after_segment``), ``drain`` (``drain.records``, ``drain.state``,
``drain.summarize``, ``hook`` around ``_on_complete``) and one ``queued``
per request — with its transfer counters, ``waves_admitted``,
``fresh_state_reuses`` (fresh waves that took the engine's constant
checkpoint in ``admit.init_state``; resumed waves and continuous refills
do not count) and the requests' ``t_submit`` / ``t_admit`` / ``t_done``.
``None``, the default, costs one test per site.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.platform_jax import (PlatformState, platform_init,
                                     spec_from_platform, stack_states,
                                     summarize)
from repro.core.tasks import (TaskArrays, invalid_task_arrays,
                              kind_period_table, pad_route_batch,
                              pad_task_arrays, route_deadline_budget,
                              stack_task_arrays, tasks_to_arrays)
from repro.serve.policy import (QoSPolicy, effective_deadline,
                                power_of_two_bucket)
from repro.serve.tracing import OFF

__all__ = [
    "QoSConfig", "QoSPlacementEngine", "RouteRequest", "Wave", "QoSPolicy",
    "power_of_two_bucket", "effective_deadline",
    "QUEUED", "RUNNING", "PREEMPTED", "COMPLETED", "SHED",
]

QUEUED = "queued"
RUNNING = "running"
PREEMPTED = "preempted"
COMPLETED = "completed"
SHED = "shed"

# A long-lived serving process churns platforms/meshes; the compiled
# segment closures it no longer uses must not accumulate forever.
_SEG_FN_CACHE_CAP = 8
_SEG_FN_CACHE: "OrderedDict" = OrderedDict()


def _seg_cache_get(key, build):
    """LRU-bounded lookup into the shared compiled-closure cache."""
    if key in _SEG_FN_CACHE:
        _SEG_FN_CACHE.move_to_end(key)
        return _SEG_FN_CACHE[key]
    fn = build()
    _SEG_FN_CACHE[key] = fn
    while len(_SEG_FN_CACHE) > _SEG_FN_CACHE_CAP:
        _SEG_FN_CACHE.popitem(last=False)
    return fn


def _segment_fn(spec, backlog_scale: float, mesh=None):
    """Jitted vmapped resume-able scan segment, cached on the table
    contents (two engines over the same platform share one compiled
    closure — the benchmark builds six engines per run).  With ``mesh``
    the lane axis is shard_mapped over the mesh's route axis; callers
    pad lanes to the mesh size."""
    key = (np.asarray(spec.exec_time).tobytes(),
           np.asarray(spec.energy).tobytes(), float(backlog_scale),
           mesh)

    def build():
        from repro.core.flexai.engine import _schedule_run
        run = _schedule_run(spec, backlog_scale)
        vm = jax.vmap(run, in_axes=(None, 0, 0))
        if mesh is not None:
            from jax.sharding import PartitionSpec as P

            ax = mesh.axis_names[0]
            vm = jax.shard_map(vm, mesh=mesh, in_specs=(P(), P(ax), P(ax)),
                               out_specs=(P(ax), P(ax)))

        def placement_segment(params, tasks, state):
            return vm(params, tasks, state)

        return jax.jit(placement_segment)

    return _seg_cache_get(key, build)


def _pipeline_segment_fn(spec, plan, backlog_scale: float):
    """Jitted vmapped pipeline segment (``core.pipeline``): lanes share
    the flat stage sequence, each carries its own (state, ring)
    checkpoint.  Cached like :func:`_segment_fn`, with the stage plan in
    the key."""
    key = (np.asarray(spec.exec_time).tobytes(),
           np.asarray(plan.stage_exec).tobytes(),
           np.asarray(plan.groups).tobytes(), float(backlog_scale))

    def build():
        from repro.core.pipeline import _pipeline_segment_run
        run = _pipeline_segment_run(spec, plan, backlog_scale,
                                    policy="flexai")
        vm = jax.vmap(run, in_axes=(None, 0, None, 0, 0))

        def pipeline_segment(params, tasks, stages, state, ring):
            return vm(params, tasks, stages, state, ring)

        return jax.jit(pipeline_segment)

    return _seg_cache_get(key, build)


@dataclasses.dataclass(frozen=True)
class QoSConfig:
    """Knobs of the deadline-aware serving layer.

    ``policy="fifo"`` reproduces the pre-QoS engine exactly (oldest-head
    bucket admission, no aging / shedding / preemption) — the baseline the
    benchmark and the dominance property compare EDF against.
    """
    policy: str = "edf"              # "edf" | "fifo"
    deadline_scale: float = 1.0      # scales the Table-5 budget
    aging_credit: float = 0.002      # s of effective-deadline credit/wave
    laxity_s: float = 0.005          # preempt when a waiter is tighter by >
    preempt: bool = True
    shed: bool = True
    slots: int = 4                   # requests per wave
    chunk: int = 16                  # tasks per service segment (preemption
                                     # granularity; must divide the bucket)
    svc_per_task: Optional[float] = None  # virtual s per lockstep task slot
                                     # (None: half the mean Table-5 period)
    min_bucket: int = 16             # power of two, >= chunk
    max_preemptions: int = 4         # per wave (livelock guard)
    stages: int = 1                  # >1: pipeline waves (core.pipeline)
    continuous: bool = False         # refill freed lanes at segment
                                     # boundaries instead of draining
    measured_svc: bool = False       # EMA-calibrated measured service
                                     # times (virtual clock = fallback)
    svc_ema: float = 0.25            # EMA weight of a new measurement

    def __post_init__(self):
        if self.policy not in ("edf", "fifo"):
            raise ValueError(f"unknown policy {self.policy!r}")
        if self.chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {self.chunk}")
        if self.min_bucket < 1:
            raise ValueError(
                f"min_bucket must be >= 1, got {self.min_bucket}")
        if self.min_bucket & (self.min_bucket - 1):
            raise ValueError(
                f"min_bucket must be a power of two, got {self.min_bucket}")
        if self.min_bucket % self.chunk:
            raise ValueError("min_bucket must be a multiple of chunk")
        if self.stages < 1:
            raise ValueError("stages must be >= 1")
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if not (0.0 < self.svc_ema <= 1.0):
            raise ValueError(f"svc_ema must be in (0, 1], got {self.svc_ema}")
        if self.continuous and self.stages > 1:
            raise ValueError(
                "continuous batching refills lockstep lanes; pipeline "
                "waves (stages > 1) drain — pick one")


@dataclasses.dataclass
class RouteRequest:
    """One vehicle's placement request plus its QoS bookkeeping."""
    uid: int
    tasks: TaskArrays        # padded to ``bucket``
    n_tasks: int             # real (pre-padding) length
    arrival: float           # virtual submit time
    deadline: float          # absolute virtual deadline
    bucket: int
    submit_order: int = 0
    waves_waited: int = 0    # admission rounds passed over (aging input)
    status: str = QUEUED
    finish: Optional[float] = None
    slack: Optional[float] = None
    summary: Optional[dict] = None
    # host perf_counter seconds, set only while a tracer is attached:
    # submitted, first admitted to a wave, placements on the host
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    t_done: Optional[float] = None

    @property
    def missed(self) -> bool:
        return self.status == SHED or (self.slack is not None
                                       and self.slack < 0.0)


@dataclasses.dataclass
class Wave:
    """An admitted (and possibly checkpointed) lockstep wave.

    Pipeline waves (``cfg.stages > 1``) carry the flat wavefront stream
    in ``batch`` ([slots, flat_len]) plus the shared stage sequence and
    the per-lane ring of upstream finish times — ``(state, ring)`` is the
    preemption checkpoint there."""
    requests: list           # lane-aligned RouteRequests (may be < slots)
    batch: TaskArrays        # [slots, bucket] (or [slots, flat_len])
    state: PlatformState     # [slots, ...] — THE preemption checkpoint
    bucket: int
    progress: int = 0        # lockstep task slots already served
    preemptions: int = 0
    waves_waited: int = 0
    recs: list = dataclasses.field(default_factory=list)
    s_seq: Optional[np.ndarray] = None   # [flat_len] stage per flat slot
    ring: Optional[jax.Array] = None     # [slots, S] checkpoint half 2
    flat_len: Optional[int] = None       # padded wavefront length
    # continuous batching (cfg.continuous): per-lane occupancy — the
    # checkpoint widens to (state, lane cursors) but stays on the Wave,
    # so preempt/resume is unchanged
    lane_requests: Optional[list] = None  # [slots] RouteRequest | None
    lane_progress: Optional[list] = None  # [slots] slots served per lane
    lane_recs: Optional[list] = None      # [slots] per-lane record chunks

    def min_deadline(self, aging_credit: float) -> float:
        return min(effective_deadline(r.deadline, self.waves_waited,
                                      aging_credit)
                   for r in self.requests)


def _start_host_copy(tree) -> int:
    """Start the device-to-host copy of every ``jax.Array`` leaf of
    ``tree`` and return at once; returns how many were started.  A later
    ``np.asarray`` / ``jax.device_get`` of the leaf reads the landed
    buffer instead of paying its own blocking round trip.  NumPy leaves
    (the stub executor's) are already on the host and are skipped."""
    n = 0
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.Array):
            x.copy_to_host_async()
            n += 1
    return n


def _stub_executor(spec):
    """State pass-through executor: same shapes as the scan dispatch, zero
    device work.  Lets the property suite exercise the queueing discipline
    (conservation / aging / dominance) at hypothesis speed."""
    from repro.core.platform_jax import StepRecord

    def seg(params, tasks, state):
        v = np.asarray(tasks.valid)
        z = np.zeros(v.shape, np.float32)
        rec = StepRecord(action=z.astype(np.int32), start=z, finish=z,
                         wait=z, exec_time=z, response=z, ms=z, energy=z,
                         met=np.zeros(v.shape, bool),
                         valid=np.zeros(v.shape, bool))
        # lax.scan stacks records time-major then the engine transposes;
        # the stub is already [lanes, chunk], so hand it over as-is
        return state, rec

    return seg


class QoSPlacementEngine:
    """Deadline-aware wave serving of FlexAI placement requests.

    One wave runs at a time (the serving pipe is the shared accelerator
    pool); a wave is up to ``slots`` same-bucket requests scheduled in
    lockstep segments of ``chunk`` tasks through the vmapped greedy scan
    engine.  Between segments the engine may preempt: the batched
    ``PlatformState`` is the checkpoint, and the wave re-enters admission
    as a resumable unit.
    """

    def __init__(self, platform, params, cfg: QoSConfig = QoSConfig(), *,
                 backlog_scale: float = 1.0,
                 executor: "Callable | str | None" = None,
                 mesh=None):
        self.spec = spec_from_platform(platform)
        self.params = params
        self.cfg = cfg
        self.backlog_scale = backlog_scale
        self.qpolicy = QoSPolicy(policy=cfg.policy,
                                 aging_credit=cfg.aging_credit,
                                 shed=cfg.shed)
        self.mesh = mesh
        if mesh is not None and cfg.stages > 1:
            raise ValueError("sharded waves are single-stage; pipeline "
                             "waves have their own 2-D mesh path")
        if mesh is not None and executor is not None:
            raise ValueError("mesh sharding requires the device scan "
                             "executor; stub/custom executors are host "
                             "functions")
        self.svc = (cfg.svc_per_task if cfg.svc_per_task is not None
                    else 0.5 * float(kind_period_table().mean()))
        # a flat pipeline slot is one (task, stage) micro-step: charge
        # svc/stages so a wave's total service matches its unpipelined
        # twin up to the (S-1)-column drain bubble
        self.svc_step = self.svc / cfg.stages
        self.base_svc = self.svc
        self.svc_scale = 1.0
        self.health = np.ones(self.spec.n, np.float64)
        self.plan = None
        if cfg.stages > 1:
            if executor is not None:
                raise ValueError(
                    "pipeline waves (stages > 1) require the device scan "
                    "executor; stub/custom executors are single-stage")
            from repro.core.pipeline import build_stage_plan
            self.plan = build_stage_plan(platform, cfg.stages)
            self._seg_fn = _pipeline_segment_fn(self.spec, self.plan,
                                                backlog_scale)
        elif executor == "stub":
            self._seg_fn = _stub_executor(self.spec)
        elif executor is not None:
            self._seg_fn = executor
        else:
            self._seg_fn = _segment_fn(self.spec, backlog_scale, mesh=mesh)
        # a fresh wave's checkpoint is one constant: nothing donates or
        # writes into a state leaf (a wave's state is only replaced), so
        # every wave ``_pack_wave`` admits takes these same arrays
        self._fresh_state = stack_states(
            [platform_init(self.spec.n)] * cfg.slots)
        self._fresh_ring = None
        if cfg.stages > 1:
            import jax.numpy as jnp
            self._fresh_ring = jnp.zeros((cfg.slots, cfg.stages),
                                         jnp.float32)
        # measured service times: per-(bucket, stages) EMA of wall-clock
        # per-slot segment cost (cfg.measured_svc); None entries fall
        # back to the virtual constant until the first dispatch lands
        self._svc_measured: dict = {}
        self._seg_elapsed: Optional[float] = None
        self.now = 0.0
        self._halt = False  # set by a durability hook to stop serving
        self._order = 0
        self.pending: list[RouteRequest] = []    # arrival > now
        self.backlog: list[RouteRequest] = []    # eligible, never started
        self.preempted: list[Wave] = []
        self.completed: list[RouteRequest] = []
        self.dead_letter: list[dict] = []
        self.wave_log: list[list[int]] = []
        self.dispatches = 0
        self.preemption_count = 0
        self.refills = 0
        self._tracer = None

    @property
    def tracer(self):
        """The attached ``serve.tracing.Tracer``, or None (off)."""
        return self._tracer

    @tracer.setter
    def tracer(self, tracer) -> None:
        if self._tracer is not None:
            self._tracer.detach()
        self._tracer = tracer
        if tracer is not None:
            tracer.attach()

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------

    def _bucket(self, n: int) -> int:
        return power_of_two_bucket(n, max(self.cfg.min_bucket,
                                          self.cfg.chunk))

    def _flat_len(self, bucket: int) -> int:
        """Wavefront stream length for a bucket, padded to a chunk
        multiple (segment cuts stay aligned)."""
        L = (bucket + self.cfg.stages - 1) * self.cfg.stages
        return L + (-L) % self.cfg.chunk

    def _service_need(self, bucket: int) -> float:
        """Service time a bucket will be charged end to end — what
        shedding and preemption decisions compare against deadlines
        (identical to ``bucket * svc`` when stages == 1).  ``set_health``
        stretches ``svc``, so a degraded pool's need grows and admission
        sheds what no longer fits *before* dispatch.  Under
        ``cfg.measured_svc`` the per-(bucket, stages) EMA of measured
        per-slot cost replaces the virtual constant once calibrated
        (still scaled by the health stretch)."""
        length = (self._flat_len(bucket) if self.cfg.stages > 1
                  else bucket)
        if self.cfg.measured_svc:
            m = self._svc_measured.get((bucket, self.cfg.stages))
            if m is not None:
                return length * m * self.svc_scale
        if self.cfg.stages > 1:
            return length * self.svc_step
        return bucket * self.svc

    def set_health(self, health) -> None:
        """Degradation-aware admission: install a per-core health row
        (``core.faults`` semantics — 0.0 dead, (0, 1] capacity fraction)
        and stretch the virtual service cost by the lost throughput.
        The lockstep wave only moves as fast as the pool's surviving
        capacity, so effective service time scales by
        total-capacity / health-weighted-capacity; ``_service_need``
        then reflects what the degraded pool can actually deliver and
        timeout shedding fires ahead of doomed dispatches.  An all-ones
        row restores the healthy cost exactly."""
        self.health = np.asarray(health, np.float64)
        et = np.asarray(self.spec.exec_time, np.float64)
        cap = 1.0 / et.mean(axis=1)          # per-core healthy throughput
        eff = float((cap * self.health).sum())
        self.svc_scale = float(cap.sum()) / max(eff, 1e-12)
        self.svc = self.base_svc * self.svc_scale
        self.svc_step = self.svc / self.cfg.stages

    def submit(self, tasks, arrival: float = 0.0,
               deadline: Optional[float] = None) -> RouteRequest:
        """Queue one route.  ``deadline`` defaults to arrival + the
        Table-5 period budget of the route (``route_deadline_budget``
        scaled by ``cfg.deadline_scale``)."""
        ta = tasks if isinstance(tasks, TaskArrays) else tasks_to_arrays(tasks)
        n = ta.num_tasks
        bucket = self._bucket(n)
        if deadline is None:
            deadline = arrival + route_deadline_budget(
                ta, self.cfg.deadline_scale)
        req = RouteRequest(uid=self._order, tasks=pad_task_arrays(ta, bucket),
                           n_tasks=n, arrival=float(arrival),
                           deadline=float(deadline), bucket=bucket,
                           submit_order=self._order)
        if self._tracer is not None:
            req.t_submit = time.perf_counter_ns() * 1e-9
        self._order += 1
        if req.arrival <= self.now:
            self.backlog.append(req)
        else:
            self.pending.append(req)
            self.pending.sort(key=lambda r: (r.arrival, r.submit_order))
        return req

    # ------------------------------------------------------------------
    # admission
    # ------------------------------------------------------------------

    def _promote_arrivals(self) -> None:
        while self.pending and self.pending[0].arrival <= self.now:
            self.backlog.append(self.pending.pop(0))

    def _eff_deadline(self, req: RouteRequest) -> float:
        return self.qpolicy.eff_deadline(req.deadline, req.waves_waited)

    def _shed_request(self, r: RouteRequest, reason: str,
                      needed_s: float) -> None:
        """Move one request to the dead-letter log (shared by queued-shed
        and the continuous-mode mid-flight overrun shed)."""
        r.status = SHED
        r.finish = self.now
        r.slack = r.deadline - self.now
        self.dead_letter.append({
            "uid": r.uid, "n_tasks": r.n_tasks,
            "deadline": r.deadline, "shed_at": self.now,
            "reason": reason, "needed_s": needed_s,
            "had_s": r.deadline - self.now})

    def _shed_infeasible(self) -> None:
        """Timeout shedding: a queued request whose full service no longer
        fits before its deadline goes to the dead-letter log (it would
        only burn a wave that a feasible request could use)."""
        keep = []
        for r in self.backlog:
            need = self._service_need(r.bucket)
            if self.qpolicy.should_shed(self.now, need, r.deadline):
                self._shed_request(r, "infeasible", need)
            else:
                keep.append(r)
        self.backlog = keep

    def _pack_wave(self, head: RouteRequest) -> Wave:
        """The head picks the bucket; the wave fills with that bucket's
        eligible requests — EDF order under "edf", submit order under
        "fifo".  Everyone left behind ages one wave."""
        peers = [r for r in self.backlog if r.bucket == head.bucket]
        peers.sort(key=self.qpolicy.request_key)
        wave_reqs = peers[: self.cfg.slots]
        taken = {r.uid for r in wave_reqs}
        self.backlog = [r for r in self.backlog if r.uid not in taken]
        self.qpolicy.age(self.backlog)
        self.qpolicy.age(self.preempted)
        for r in wave_reqs:
            r.status = RUNNING
        tr = self._tracer
        s_seq = flat_len = None
        with OFF if tr is None else tr.span("admit.pack_tasks"):
            rows = [r.tasks for r in wave_reqs]
            rows += [invalid_task_arrays(head.bucket)
                     for _ in range(self.cfg.slots - len(rows))]
            batch = stack_task_arrays(rows)
            if self.plan is not None:
                batch, s_seq, flat_len = self._flatten_batch(batch,
                                                             head.bucket)
        with OFF if tr is None else tr.span("admit.init_state"):
            state, ring = self._fresh_state, self._fresh_ring
            if tr is not None:
                tr.count("fresh_state_reuses")
        self._admitted(wave_reqs)
        # the wave inherits its members' earned aging credit, so a
        # long-aged request that gets preempted right after admission does
        # not restart its anti-starvation clock from zero
        return Wave(requests=wave_reqs, batch=batch, state=state,
                    bucket=head.bucket,
                    waves_waited=max(r.waves_waited for r in wave_reqs),
                    s_seq=s_seq, ring=ring, flat_len=flat_len)

    def _flatten_batch(self, batch: TaskArrays, bucket: int):
        """[slots, bucket] lockstep batch -> [slots, flat_len] wavefront
        stream (``core.pipeline._wavefront_stream`` per lane; the stage
        sequence depends only on (bucket, stages), so lanes share it),
        right-padded with invalid rows to a chunk multiple."""
        from repro.core.pipeline import _wavefront_stream
        S = self.cfg.stages
        flat_len = self._flat_len(bucket)
        lanes, s_seq = [], None
        for lane in range(batch.arrival.shape[0]):
            rows, ss = _wavefront_stream(
                jax.tree_util.tree_map(lambda a: a[lane], batch), S)
            rows = jax.tree_util.tree_map(np.asarray, rows)
            lanes.append(pad_task_arrays(rows, flat_len))
            s_seq = ss
        s_seq = np.concatenate(
            [np.asarray(s_seq),
             np.zeros(flat_len - s_seq.shape[0], s_seq.dtype)])
        return stack_task_arrays(lanes), s_seq, flat_len

    def _admitted(self, reqs: list) -> None:
        """Log one admission round; with a tracer, stamp the first
        admission of each request and record its ``queued`` span."""
        self.wave_log.append([r.uid for r in reqs])
        tr = self._tracer
        if tr is None:
            return
        tr.count("waves_admitted")
        wid = len(self.wave_log) - 1
        t = time.perf_counter_ns()
        for r in reqs:
            if r.t_admit is None:
                r.t_admit = t * 1e-9
                if r.t_submit is not None:
                    tr.record("queued", round(r.t_submit * 1e9), t,
                              wave=wid, uid=r.uid)

    def _next_wave(self) -> Optional[Wave]:
        """Admit the next wave (None when nothing is left to serve)."""
        tr = self._tracer
        if tr is None:
            return self._select_wave()
        # the round about to be logged, so the children carry it too
        with tr.span("admit", wave=len(self.wave_log)) as sp:
            wave = self._select_wave()
            if wave is None:
                sp.wave = None
        return wave

    def _select_wave(self) -> Optional[Wave]:
        while True:
            self._promote_arrivals()
            if not self.backlog and not self.preempted:
                if not self.pending:
                    return None
                self.now = max(self.now, self.pending[0].arrival)
                self._promote_arrivals()
            if self.cfg.policy == "edf" and self.cfg.shed:
                self._shed_infeasible()
            if self.backlog or self.preempted:
                break
            if not self.pending:  # everything left was shed
                return None
            # an all-infeasible arrival group was shed; advance to the next
        if self.cfg.policy == "fifo":
            if self.preempted:      # only reachable via external injection:
                # _should_preempt gates on "edf", but resume consistently
                return self._resume(self.preempted[0])
            head = min(self.backlog, key=lambda r: r.submit_order)
            return self._pack_wave(head)
        # EDF: fresh requests and preempted waves compete on effective
        # deadline; a resumed wave re-enters at its checkpoint
        best_req = min(self.backlog, default=None,
                       key=self.qpolicy.request_key)
        best_wave = min(self.preempted, default=None,
                        key=lambda w: w.min_deadline(self.cfg.aging_credit))
        if best_wave is not None and (
                best_req is None
                or best_wave.min_deadline(self.cfg.aging_credit)
                <= self._eff_deadline(best_req)):
            return self._resume(best_wave)
        return self._pack_wave(best_req)

    def _resume(self, wave: Wave) -> Wave:
        """Re-admit a preempted wave at its checkpoint: same aging and
        wave_log bookkeeping as a fresh admission."""
        self.preempted.remove(wave)
        self.qpolicy.age(self.backlog)
        self.qpolicy.age(self.preempted)
        for r in wave.requests:
            r.status = RUNNING
        self._admitted(wave.requests)
        return wave

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def _should_preempt(self, wave: Wave) -> bool:
        if (self.cfg.policy != "edf" or not self.cfg.preempt
                or wave.preemptions >= self.cfg.max_preemptions):
            return False
        # a waiter that can no longer make its deadline anyway (it will be
        # shed at the next admission) is not worth a checkpoint
        waiters = [self._eff_deadline(r) for r in self.backlog
                   if not self.qpolicy.should_shed(
                       self.now, self._service_need(r.bucket), r.deadline)]
        waiters += [w.min_deadline(self.cfg.aging_credit)
                    for w in self.preempted]
        if not waiters:
            return False
        return min(waiters) < (wave.min_deadline(self.cfg.aging_credit)
                               - self.cfg.laxity_s)

    # ---- durability seams (overridden by serve/durability.py) ----------

    def _stage_slice(self, wave: Wave) -> np.ndarray:
        """The stage sequence of a pipeline wave's next segment."""
        return wave.s_seq[wave.progress: wave.progress + self.cfg.chunk]

    def _dispatch_segment(self, wave: Wave, seg: TaskArrays):
        """Serve one chunk: returns ``(new_state, records)``; a pipeline
        segment also advances the wave's ring.  The durability layer
        swaps in fault-masked / mesh-sharded executors here without
        touching the wave loop.  With a mesh the lane axis is padded to
        the mesh size (invalid rows + fresh states) and trimmed back —
        per-lane scans are independent, so sharding is
        placement-neutral."""
        if self.plan is not None:
            state, wave.ring, recs = self._seg_fn(
                self.params, seg, self._stage_slice(wave), wave.state,
                wave.ring)
            return state, recs
        if self.mesh is not None:
            pad = (-self.cfg.slots) % self.mesh.size
            if pad:
                import jax.numpy as jnp
                seg = pad_route_batch(seg, self.mesh.size)
                state = jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate(
                        [jnp.asarray(a), jnp.asarray(b)]),
                    wave.state,
                    stack_states([platform_init(self.spec.n)] * pad))
                st, recs = self._seg_fn(self.params, seg, state)
                trim = lambda a: a[: self.cfg.slots]  # noqa: E731
                return (jax.tree_util.tree_map(trim, st),
                        jax.tree_util.tree_map(trim, recs))
        return self._seg_fn(self.params, seg, wave.state)

    def _timed_dispatch(self, wave: Wave, seg: TaskArrays):
        """Dispatch one segment (the ``segment.call`` span), measuring
        wall time when the measured service clock is armed: the blocking
        ``perf_counter`` window feeds the per-(bucket, stages) EMA and is
        what ``_charge_segment`` advances the clock by for this
        segment."""
        tr = self._tracer
        with OFF if tr is None else tr.span("segment.call"):
            if tr is not None:
                tr.to_device(self.params, seg, wave.state, *(
                    () if self.plan is None
                    else (self._stage_slice(wave), wave.ring)))
            if not self.cfg.measured_svc:
                return self._dispatch_segment(wave, seg)
            t0 = time.perf_counter()
            out = self._dispatch_segment(wave, seg)
            jax.block_until_ready(out[0])
            self._seg_elapsed = time.perf_counter() - t0
            self._observe_service(wave.bucket, self._seg_elapsed)
            return out

    def _observe_service(self, bucket: int, elapsed: float) -> None:
        per_slot = elapsed / self.cfg.chunk
        key = (bucket, self.cfg.stages)
        prev = self._svc_measured.get(key)
        a = self.cfg.svc_ema
        self._svc_measured[key] = (per_slot if prev is None
                                   else (1.0 - a) * prev + a * per_slot)

    def _charge_segment(self, wave: Wave, recs) -> None:
        """Advance the clock for one served segment (the durability layer
        charges degraded-core overruns here).  Pipeline segments are
        chunks of flat (task, stage) micro-steps charged at
        ``svc/stages`` each — identical to ``chunk * svc`` at one stage.
        A measured segment charges its own blocking wall time instead of
        the virtual constant."""
        if self._seg_elapsed is not None:
            self.now += self._seg_elapsed
            self._seg_elapsed = None
        else:
            self.now += self.cfg.chunk * self.svc_step

    def _after_segment(self, wave: Wave) -> None:
        """Segment-boundary hook: fault firing, heartbeats, snapshot
        cadence, preemption-guard checks (no-op in the base engine)."""

    def _on_complete(self, req: RouteRequest, lane_final, lane_recs) -> None:
        """Per-request completion hook (durability: final-state capture
        for the recovery parity digest)."""

    # --------------------------------------------------------------------

    def _run_wave(self, wave: Wave) -> None:
        if self.cfg.continuous and self.plan is None:
            return self._run_wave_continuous(wave)
        chunk = self.cfg.chunk
        total = wave.flat_len if wave.flat_len is not None else wave.bucket
        while wave.progress < total:
            tr = self._tracer
            with (OFF if tr is None
                  else tr.span("segment", wave=len(self.wave_log) - 1)):
                p = wave.progress
                with OFF if tr is None else tr.span("segment.slice"):
                    seg = jax.tree_util.tree_map(
                        lambda a: a[:, p: p + chunk], wave.batch)
                state, recs = self._timed_dispatch(wave, seg)
                # the records travel to the host behind the device's
                # compute while the host dispatches on; the drain reads
                # buffers that have landed
                early = _start_host_copy(recs)
                if tr is not None:
                    tr.to_host(recs)
                    tr.count("d2h_early", early)
                self.dispatches += 1
                wave.state = state
                wave.recs.append(recs)
                wave.progress += chunk
                self._charge_segment(wave, recs)
                self._promote_arrivals()
                with OFF if tr is None else tr.span("hook"):
                    self._after_segment(wave)
                if self._halt:
                    return  # durability stop: the wave was snapshotted
                if wave.progress < total and self._should_preempt(wave):
                    wave.preemptions += 1
                    self.preemption_count += 1
                    for r in wave.requests:
                        r.status = PREEMPTED
                    self.preempted.append(wave)
                    return
        self._drain_wave(wave)

    def _drain_wave(self, wave: Wave) -> None:
        """Wave drained: bring its records and final state to the host;
        every live lane completes at the current clock.  The records'
        copies were started at dispatch (``_run_wave``), where they are
        counted."""
        tr = self._tracer
        with (OFF if tr is None
              else tr.span("drain", wave=len(self.wave_log) - 1)):
            with OFF if tr is None else tr.span("drain.records"):
                recs = jax.tree_util.tree_map(
                    lambda *xs: np.concatenate(xs, axis=1),
                    *jax.device_get(wave.recs))
            with OFF if tr is None else tr.span("drain.state"):
                if tr is not None:
                    tr.to_host(wave.state)
                final = jax.device_get(wave.state)
            order = None
            if self.plan is not None:
                from repro.core.pipeline import _record_order
                order = np.asarray(_record_order(wave.bucket,
                                                 self.cfg.stages))
            for lane, req in enumerate(wave.requests):
                with OFF if tr is None else tr.span("drain.summarize",
                                                    uid=req.uid):
                    lane_final = jax.tree_util.tree_map(lambda a: a[lane],
                                                        final)
                    lane_recs = jax.tree_util.tree_map(lambda a: a[lane],
                                                       recs)
                    if order is not None:
                        # flat wavefront records -> task-major [bucket, S];
                        # end-to-end verdicts come from the final stage
                        from repro.core.pipeline import pipeline_summarize
                        lane_recs = jax.tree_util.tree_map(
                            lambda a: a[order], lane_recs)
                        summ = pipeline_summarize(self.spec, lane_final,
                                                  lane_recs)
                    else:
                        summ = summarize(self.spec, lane_final, lane_recs)
                    # [n_tasks] placements ([n_tasks, S] for pipelines)
                    summ["placements"] = np.asarray(
                        lane_recs.action)[: req.n_tasks]
                    summ["bucket"] = wave.bucket
                self._finish(req, summ, lane_final, lane_recs)

    def _finish(self, req: RouteRequest, summ: dict, lane_final,
                lane_recs) -> None:
        """A request's placements are on the host: complete it (the
        ``_on_complete`` hook runs in a ``hook`` span)."""
        req.summary = summ
        req.status = COMPLETED
        req.finish = self.now
        req.slack = req.deadline - self.now
        tr = self._tracer
        if tr is not None:
            req.t_done = time.perf_counter_ns() * 1e-9
        with OFF if tr is None else tr.span("hook", uid=req.uid):
            self._on_complete(req, lane_final, lane_recs)
        self.completed.append(req)

    # ---- continuous batching (cfg.continuous) --------------------------

    def _run_wave_continuous(self, wave: Wave) -> None:
        """Continuous-batching wave loop (JetStream prefill-insert
        style): lanes carry independent cursors, and at every segment
        boundary a freed lane — completed, or shed mid-flight once its
        remaining service cannot meet its deadline — is refilled from
        the backlog with a reinitialized ``PlatformState`` row.  The
        wave stays a preemptible checkpointed unit: ``(state, lane
        cursors)`` lives on the Wave, so preempt/resume re-enters here
        unchanged."""
        chunk, slots = self.cfg.chunk, self.cfg.slots
        if wave.lane_requests is None:
            wave.lane_requests = (list(wave.requests)
                                  + [None] * (slots - len(wave.requests)))
            wave.lane_progress = [0] * slots
            wave.lane_recs = [[] for _ in range(slots)]
        idle_row = invalid_task_arrays(chunk)
        while True:
            tr = self._tracer
            with (OFF if tr is None
                  else tr.span("segment", wave=len(self.wave_log) - 1)):
                with OFF if tr is None else tr.span("segment.slice"):
                    rows = []
                    for lane in range(slots):
                        r = wave.lane_requests[lane]
                        if r is None:
                            rows.append(idle_row)
                        else:
                            p = wave.lane_progress[lane]
                            rows.append(jax.tree_util.tree_map(
                                lambda a: a[p: p + chunk], r.tasks))
                    seg = stack_task_arrays(rows)
                state, recs = self._timed_dispatch(wave, seg)
                self.dispatches += 1
                wave.state = state
                for lane in range(slots):
                    if wave.lane_requests[lane] is not None:
                        wave.lane_recs[lane].append(jax.tree_util.tree_map(
                            lambda a: a[lane], recs))
                        wave.lane_progress[lane] += chunk
                wave.progress += chunk
                self._charge_segment(wave, recs)
                self._promote_arrivals()
                with OFF if tr is None else tr.span("hook"):
                    self._after_segment(wave)
            if self._halt:
                wave.requests = [r for r in wave.lane_requests
                                 if r is not None]
                return
            for lane in range(slots):
                r = wave.lane_requests[lane]
                if (r is not None
                        and wave.lane_progress[lane] >= wave.bucket):
                    self._complete_lane(wave, lane)
            self._shed_overrun_lanes(wave)
            self._refill(wave)
            wave.requests = [r for r in wave.lane_requests if r is not None]
            if not wave.requests:
                return
            if self._should_preempt(wave):
                wave.preemptions += 1
                self.preemption_count += 1
                for r in wave.requests:
                    r.status = PREEMPTED
                self.preempted.append(wave)
                return

    def _complete_lane(self, wave: Wave, lane: int) -> None:
        """One lane reached its bucket: summarize exactly like a drained
        wave's lane and free the slot for refill."""
        r = wave.lane_requests[lane]
        tr = self._tracer
        with (OFF if tr is None
              else tr.span("drain", wave=len(self.wave_log) - 1)):
            with OFF if tr is None else tr.span("drain.records"):
                if tr is not None:
                    tr.to_host(wave.lane_recs[lane])
                lane_recs = jax.tree_util.tree_map(
                    lambda *xs: np.concatenate([np.asarray(x) for x in xs]),
                    *wave.lane_recs[lane])
            with OFF if tr is None else tr.span("drain.state"):
                if tr is not None:
                    tr.to_host(wave.state)
                lane_final = jax.tree_util.tree_map(
                    lambda a: a[lane], jax.device_get(wave.state))
            with OFF if tr is None else tr.span("drain.summarize",
                                                uid=r.uid):
                summ = summarize(self.spec, lane_final, lane_recs)
                summ["placements"] = np.asarray(lane_recs.action)[: r.n_tasks]
                summ["bucket"] = wave.bucket
            self._finish(r, summ, lane_final, lane_recs)
        wave.lane_requests[lane] = None
        wave.lane_progress[lane] = 0
        wave.lane_recs[lane] = []

    def _shed_overrun_lanes(self, wave: Wave) -> None:
        """Mid-flight shed: a lane whose *remaining* service can no
        longer meet its deadline is cut loose (the work already done is
        sunk either way) so the lane can serve a feasible request — the
        "shed member" source of freed lanes."""
        if not self.qpolicy.is_edf or not self.cfg.shed:
            return
        per_slot = self._service_need(wave.bucket) / wave.bucket
        for lane, r in enumerate(wave.lane_requests):
            if r is None:
                continue
            need = (wave.bucket - wave.lane_progress[lane]) * per_slot
            if self.qpolicy.should_shed(self.now, need, r.deadline):
                self._shed_request(r, "overrun", need)
                wave.lane_requests[lane] = None
                wave.lane_progress[lane] = 0
                wave.lane_recs[lane] = []

    def _refill_head(self, wave: Wave) -> Optional[RouteRequest]:
        """The request global admission would run next, or None if a
        checkpointed wave (or nothing) should go first — refill must not
        overtake the cross-bucket EDF/FIFO order, or aging's starvation
        bound dies."""
        if not self.backlog:
            return None
        if not self.qpolicy.is_edf:
            if self.preempted:
                return None
            return min(self.backlog, key=lambda r: r.submit_order)
        best_req = min(self.backlog, key=self.qpolicy.request_key)
        best_wave = min(self.preempted, default=None,
                        key=lambda w: w.min_deadline(self.cfg.aging_credit))
        if best_wave is not None and (
                best_wave.min_deadline(self.cfg.aging_credit)
                <= self._eff_deadline(best_req)):
            return None
        return best_req

    def _refill(self, wave: Wave) -> None:
        """Admit backlog into freed lanes at a segment boundary.  Only
        the global admission head is eligible, and only while it shares
        the wave's bucket; a refill round that admits anyone counts as
        an admission round for aging (everyone passed over earns a
        wave of credit, same as ``_pack_wave``)."""
        free = [lane for lane in range(self.cfg.slots)
                if wave.lane_requests[lane] is None]
        if not free:
            return
        tr = self._tracer
        with (OFF if tr is None
              else tr.span("admit", wave=len(self.wave_log))) as sp:
            admitted = self._refill_lanes(wave, free)
            if tr is not None and not admitted:
                sp.wave = None

    def _refill_lanes(self, wave: Wave, free: list) -> list:
        if self.qpolicy.is_edf and self.cfg.shed:
            self._shed_infeasible()
        import jax.numpy as jnp
        tr = self._tracer
        admitted = []
        for lane in free:
            head = self._refill_head(wave)
            if head is None or head.bucket != wave.bucket:
                break
            self.backlog.remove(head)
            head.status = RUNNING
            wave.lane_requests[lane] = head
            wave.lane_progress[lane] = 0
            wave.lane_recs[lane] = []
            with OFF if tr is None else tr.span("admit.init_state"):
                wave.state = jax.tree_util.tree_map(
                    lambda a, b: jnp.asarray(a).at[lane].set(b),
                    wave.state, platform_init(self.spec.n))
            admitted.append(head)
        if admitted:
            self.refills += len(admitted)
            self._admitted(admitted)
            self.qpolicy.age(self.backlog)
            self.qpolicy.age(self.preempted)
            wave.waves_waited = max(
                [wave.waves_waited] + [r.waves_waited for r in admitted])
        return admitted

    def run_until_done(self, max_waves: int = 100_000) -> None:
        for _ in range(max_waves):
            if self._halt:
                return
            wave = self._next_wave()
            if wave is None:
                return
            self._run_wave(wave)
        raise RuntimeError(f"serving did not drain in {max_waves} waves")

    # ------------------------------------------------------------------
    # metrics
    # ------------------------------------------------------------------

    def stats(self) -> dict:
        """Serving-boundary QoS summary (what BENCH_serving.json reports).

        Safe to read mid-drain: miss/slack rates denominate over
        *resolved* requests only (completed + shed); work still pending,
        queued, or in flight is reported separately instead of silently
        deflating the miss rate (ISSUE 10 bugfix)."""
        submitted = self._order
        shed = len(self.dead_letter)
        ms = self.qpolicy.miss_stats(
            [r.slack for r in self.completed], shed)
        queued = len(self.backlog) + len(self.pending)
        in_flight = submitted - ms["resolved"] - queued
        stm = [r.summary["stm_rate"] for r in self.completed
               if r.summary is not None and r.summary["tasks"] > 0]
        # task-weighted STM over the WHOLE submitted workload: a shed
        # route's tasks were never processed, so they count as unmet —
        # this is the number the paper's "100% within period" claim maps
        # to at the serving boundary
        met_tasks = sum(r.summary["stm_rate"] * r.summary["tasks"]
                        for r in self.completed if r.summary is not None)
        total_tasks = (sum(r.n_tasks for r in self.completed)
                       + sum(d["n_tasks"] for d in self.dead_letter))
        return {
            "policy": self.cfg.policy,
            "submitted": submitted,
            "resolved": ms["resolved"],
            "in_flight": in_flight,
            "queued": queued,
            "completed": ms["completed"],
            "shed": shed,
            "missed_deadline": ms["missed_deadline"],
            "miss_rate": ms["miss_rate"],
            "p50_slack_s": ms["p50_slack"],
            "p99_slack_s": ms["p99_slack"],
            "mean_stm_rate": float(np.mean(stm)) if stm else 0.0,
            "stm_rate_incl_shed": (met_tasks / total_tasks) if total_tasks
            else 0.0,
            "waves": len(self.wave_log),
            "preemptions": self.preemption_count,
            "dispatches": self.dispatches,
            "refills": self.refills,
            "virtual_time_s": self.now,
        }
