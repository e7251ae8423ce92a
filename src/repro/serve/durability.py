"""Durability & failure recovery for the QoS serving layer (ISSUE 6).

The paper's "basically 100% of tasks within their required period" claim is
a safety claim, and safety claims have to survive failures: a killed
serving process, a re-meshed device count, a dead or degraded accelerator
mid-route (the per-chiplet fault model of arXiv:2411.16007).  This module
composes the existing pieces — the PR-5 ``PlatformState`` preemption seam,
the atomic ``AsyncCheckpointer``, ``StragglerDetector``/``PreemptionGuard``
— into a crash-recoverable serving story:

* **Snapshots** (``DurableQoSEngine.snapshot``): on a segment cadence the
  full serving state — batched ``PlatformState``, QoS queues, the running
  wave (including its partial records), wave log, dead-letter log, virtual
  clock, fault/detector state, and the policy weights — is packed into a
  flat array list plus a JSON meta blob and handed to ``AsyncCheckpointer``
  (host copy synchronous, disk write on the background thread).

* **Crash recovery** (``DurableQoSEngine.restore``): the latest snapshot is
  self-describing (``load_checkpoint_arrays`` needs no live template), so a
  fresh process rebuilds the engine mid-wave and replays deterministically.
  Every admission/preemption/shed decision is a pure function of the
  virtual clock and the queues — both in the snapshot — so the recovered
  trajectory is **bit-exact** vs an uninterrupted run (the kill-mid-wave
  subprocess test in tests/test_durability.py proves it on the served set,
  placements, and final per-request ``PlatformState``).

* **Elastic resume**: restoring with a ``("routes",)`` mesh re-pads the
  wave's lane axis to the mesh size (``pad_route_batch`` + extra
  ``platform_init`` lanes) and dispatches through a shard_mapped vmapped
  scan — snapshots are mesh-independent, so a 1-device snapshot restores
  onto N devices with placement parity.

* **Fault injection + graceful degradation** (``FaultInjection``): at a
  virtual-clock instant an accelerator degrades by ``factor`` (a large
  factor is a dead core).  Execution truth switches to the degraded spec
  for *everyone*; a ``handled`` fault additionally stops the core's
  heartbeats, the ``StragglerDetector`` (driven by the serving virtual
  clock) flags it, and mitigation masks it out of the Q argmax
  (``_schedule_run_masked``), rescales the lockstep service cost to the
  surviving capacity, and lets the QoS layer shed what no longer fits.
  The unhandled arm keeps placing onto the faulty core and pays for it
  through the segment charge ratio — the no-mitigation baseline
  ``benchmarks/recovery.py`` compares against.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flexai.dqn import DQNParams
from repro.core.flexai.engine import _schedule_run_masked
from repro.core.platform_jax import PlatformSpec, PlatformState, StepRecord
from repro.core.tasks import TaskArrays
from repro.serve.qos import (QoSConfig, QoSPlacementEngine, RouteRequest,
                             Wave)
from repro.train import checkpoint as ckpt_lib
from repro.train.fault_tolerance import (HeartbeatRecord, PreemptionGuard,
                                         StragglerDetector)

SNAPSHOT_VERSION = 2

# exec-time multiplier at/above which an injected fault counts as a dead
# core: its heartbeats stop and the detector's dead-host arm fires.  Below
# it the core is a *straggler* — it keeps heartbeating with an inflated
# step time and the detector's threshold arm flags it instead.
DEAD_CORE_FACTOR = 8.0


@dataclasses.dataclass(frozen=True)
class FaultInjection:
    """One accelerator failing (or degrading) at a virtual-clock instant.

    ``factor`` multiplies the core's exec-time/energy rows from
    ``at_time`` on (per-chiplet degradation; a large factor is a dead
    core).  ``handled=True`` lets the serving layer react — heartbeat
    silence, detector flag, alive-mask reroute, capacity-scaled shedding;
    ``handled=False`` degrades execution truth but the scheduler keeps
    placing onto the faulty core (the no-mitigation baseline).
    """
    at_time: float
    core: int
    factor: float = 50.0
    handled: bool = True


def injections_from_fault_events(events, svc_per_task: float, *,
                                 handled: bool = True
                                 ) -> list[FaultInjection]:
    """Bridge the in-scan fault schedule (``core.faults.FaultEvent``) to
    serving-time injections, so one seeded trace drives both the scan
    engines and the serving layer.

    A task-step index maps onto the virtual clock at which serving has
    charged that many lockstep task slots (``step * svc_per_task``).
    Trace factors are *capacity* (0.0 dead, (0, 1] fraction) while
    injection factors are cumulative exec-time *multipliers*, so each
    event emits the relative multiplier that moves the core from its
    previous capacity to the new one — a recovery event divides the
    earlier slowdown back out.  A dead-core event lands at the
    ``HEALTH_FLOOR`` multiplier (1000x), well past ``DEAD_CORE_FACTOR``,
    so it takes the heartbeat-silence arm exactly like a hand-written
    ``FaultInjection(factor=50)``."""
    from repro.core.platform_jax import HEALTH_FLOOR
    cur: dict[int, float] = {}
    out = []
    for ev in sorted(events, key=lambda e: (e.step, e.core)):
        prev = cur.get(ev.core, 1.0)
        new = max(float(ev.factor), HEALTH_FLOOR)
        cur[ev.core] = new
        out.append(FaultInjection(at_time=ev.step * svc_per_task,
                                  core=ev.core, factor=prev / new,
                                  handled=handled))
    return out


def degrade_spec(healthy: PlatformSpec,
                 core_factor: np.ndarray) -> PlatformSpec:
    """Execution-truth spec: per-core exec/energy rows scaled by the
    cumulative degradation factors (energy scales with busy time at fixed
    power).  The G-value scales stay at their healthy values — the metric
    normalization must not move when the platform degrades."""
    f = np.asarray(core_factor, np.float32)[:, None]
    return PlatformSpec(
        exec_time=jnp.asarray(np.asarray(healthy.exec_time) * f),
        energy=jnp.asarray(np.asarray(healthy.energy) * f),
        gvalue_e_scale=healthy.gvalue_e_scale,
        gvalue_t_scale=healthy.gvalue_t_scale)


_MASKED_FN_CACHE: dict = {}


def _masked_segment_fn(spec: PlatformSpec, backlog_scale: float, mesh=None):
    """Jitted vmapped alive-masked resume-able scan segment, optionally
    shard_mapped over ``mesh``'s route axis.  ``alive`` is a runtime
    argument, so one compiled closure serves every fault pattern; only a
    spec change (fault firing) recompiles."""
    key = (np.asarray(spec.exec_time).tobytes(),
           np.asarray(spec.energy).tobytes(), float(backlog_scale),
           mesh)
    if key not in _MASKED_FN_CACHE:
        run = _schedule_run_masked(spec, backlog_scale)

        def seg(params, tasks, state, alive):
            return run(params, tasks, state0=state, alive=alive)

        vm = jax.vmap(seg, in_axes=(None, 0, 0, None))
        if mesh is None:
            _MASKED_FN_CACHE[key] = jax.jit(vm)
        else:
            from jax.sharding import PartitionSpec as P

            ax = mesh.axis_names[0]
            _MASKED_FN_CACHE[key] = jax.jit(jax.shard_map(
                vm, mesh=mesh, in_specs=(P(), P(ax), P(ax), P()),
                out_specs=(P(ax), P(ax))))
    return _MASKED_FN_CACHE[key]


def _py(v):
    return v.item() if isinstance(v, (np.floating, np.integer,
                                      np.bool_)) else v


def _sanitize(d: dict) -> dict:
    return {k: _py(v) for k, v in d.items()}


# ---------------------------------------------------------------------------
# snapshot pack / unpack
# ---------------------------------------------------------------------------

def pack_engine(eng: "DurableQoSEngine", inflight: Optional[Wave] = None,
                *, host: bool = True) -> tuple[list, dict]:
    """Flatten the full serving state into ``(arrays, meta)``: a list of
    host arrays (a valid pytree for ``AsyncCheckpointer``) plus a
    JSON-serializable meta dict whose ``[start, count]`` refs index into
    the array list.  ``inflight`` is the wave currently inside
    ``_run_wave`` (it lives in no queue).

    ``host=False`` keeps device leaves as raw references instead of
    transferring them — jax arrays are immutable, so a snapshot can
    capture them synchronously and let :func:`encode_snapshot` pay the
    device_get on the checkpoint writer thread, off the serving path."""
    arrays: list = []

    def ref(tree):
        leaves = jax.tree_util.tree_leaves(tree)
        start = len(arrays)
        arrays.extend(leaves)
        return [start, len(leaves)]

    def req_meta(r: RouteRequest) -> dict:
        m = {"uid": r.uid, "n_tasks": r.n_tasks, "arrival": _py(r.arrival),
             "deadline": _py(r.deadline), "bucket": r.bucket,
             "submit_order": r.submit_order, "waves_waited": r.waves_waited,
             "status": r.status, "finish": _py(r.finish),
             "slack": _py(r.slack), "tasks": ref(r.tasks)}
        if r.summary is not None:
            m["summary"] = {
                "scalars": _sanitize({k: v for k, v in r.summary.items()
                                      if not isinstance(v, np.ndarray)}),
                "arrays": {k: ref(v) for k, v in r.summary.items()
                           if isinstance(v, np.ndarray)}}
        return m

    def wave_meta(w: Wave) -> dict:
        recs = None
        if w.recs:
            # one ref per segment record, exactly as ``_run_wave`` holds
            # them — concatenating here would block the serving thread on
            # recent segments' device buffers
            recs = [ref(p) for p in w.recs]
        return {"requests": [req_meta(r) for r in w.requests],
                "batch": ref(w.batch), "state": ref(w.state),
                "bucket": w.bucket, "progress": w.progress,
                "preemptions": w.preemptions,
                "waves_waited": w.waves_waited, "recs": recs}

    meta = {
        "version": SNAPSHOT_VERSION,
        "now": eng.now,
        "order": eng._order,
        "dispatches": eng.dispatches,
        "preemption_count": eng.preemption_count,
        "segments_done": eng.segments_done,
        "svc": eng.svc, "base_svc": eng.base_svc,
        "svc_scale": eng.svc_scale,
        "snapshot_every": eng.snapshot_every,
        "snapshots_written": eng.snapshots_written,
        "cfg": dataclasses.asdict(eng.cfg),
        "wave_log": eng.wave_log,
        "dead_letter": [_sanitize(d) for d in eng.dead_letter],
        "pending": [req_meta(r) for r in eng.pending],
        "backlog": [req_meta(r) for r in eng.backlog],
        "preempted": [wave_meta(w) for w in eng.preempted],
        "completed": [req_meta(r) for r in eng.completed],
        "inflight": wave_meta(inflight) if inflight is not None else None,
        "alive": [bool(a) for a in eng.alive],
        "health": [float(h) for h in eng.health],
        "core_factor": [float(f) for f in eng.core_factor],
        "fired": [_sanitize(ev) for ev in eng.fired],
        "pending_faults": [dataclasses.asdict(f)
                           for f in eng.pending_faults],
        "detector_last_seen": {str(h): float(t) for h, t
                               in eng.detector._last_seen.items()},
        "detector_times": {str(h): [float(x) for x in ts] for h, ts
                           in eng.detector._times.items()},
        "final_states": {str(uid): ref(st)
                         for uid, st in eng.final_states.items()},
        "params": ref(eng.params),
        "exec_time": ref(np.asarray(eng.healthy_spec.exec_time)),
    }
    if host:
        # one batched transfer for every device leaf (np leaves pass
        # through untouched) — far cheaper than a device_get per leaf,
        # and this is serving-thread time, the snapshot-overhead budget
        arrays = [x if type(x) is np.ndarray else np.asarray(x)
                  for x in jax.device_get(arrays)]
    return arrays, meta


def _slice(arrays: list, ref_: list) -> list:
    start, n = ref_
    return arrays[start: start + n]


def encode_snapshot(arrays: list, meta: dict) -> list:
    """On-disk form of a packed snapshot: one byte blob holding every
    array back-to-back plus the JSON meta (dtype/shape per array rides in
    ``meta["leaves"]``).  Two files per snapshot instead of one per array
    — the write cost is what the <10% snapshot-overhead budget pays.
    Accepts raw device leaves from ``pack_engine(..., host=False)`` and
    materializes them here (i.e. on whichever thread runs the encode)."""
    return [_snapshot_blob(arrays), _snapshot_meta(arrays, meta)]


def _snapshot_meta(arrays: list, meta: dict) -> np.ndarray:
    """JSON half of the blob encoding.  Runs synchronously at snapshot
    time: serializing freezes any live engine containers the meta still
    references (``wave_log`` etc.) before serving mutates them further —
    dtype/shape reads never touch device buffers."""
    meta = dict(meta)
    dtype_names: dict = {}
    meta["leaves"] = [
        [dtype_names.setdefault(a.dtype, str(a.dtype)), list(a.shape)]
        for a in arrays]
    return np.frombuffer(json.dumps(meta).encode(), np.uint8)


def _snapshot_blob(arrays: list) -> np.ndarray:
    """Byte half of the blob encoding: every array back-to-back.  Safe to
    defer to the checkpoint writer thread — jax leaves are immutable and
    the engine never mutates packed host arrays in place."""
    return np.frombuffer(
        b"".join((x if type(x) is np.ndarray
                  else np.asarray(jax.device_get(x))).tobytes()
                 for x in arrays), np.uint8)


def decode_snapshot(leaves: list) -> tuple[list, dict]:
    """Inverse of :func:`encode_snapshot` -> ``(arrays, meta)``."""
    blob, meta_arr = leaves
    meta = json.loads(bytes(meta_arr).decode())
    buf, off, arrays = blob.tobytes(), 0, []
    for dt, shape in meta.pop("leaves"):
        n = int(np.prod(shape)) * np.dtype(dt).itemsize
        arrays.append(np.frombuffer(
            buf, np.dtype(dt), count=int(np.prod(shape)), offset=off
        ).reshape(shape).copy())
        off += n
    return arrays, meta


def unpack_into(eng: "DurableQoSEngine", arrays: list, meta: dict) -> None:
    """Inverse of :func:`pack_engine`: fill a freshly constructed engine
    with the snapshot's serving state."""
    def tree_from(cls, ref_, device=False):
        leaves = _slice(arrays, ref_)
        if device:
            leaves = [jnp.asarray(x) for x in leaves]
        return cls(*leaves)

    def req_from(m: dict) -> RouteRequest:
        r = RouteRequest(
            uid=m["uid"], tasks=tree_from(TaskArrays, m["tasks"]),
            n_tasks=m["n_tasks"], arrival=m["arrival"],
            deadline=m["deadline"], bucket=m["bucket"],
            submit_order=m["submit_order"],
            waves_waited=m["waves_waited"], status=m["status"],
            finish=m["finish"], slack=m["slack"])
        if m.get("summary") is not None:
            s = dict(m["summary"]["scalars"])
            for k, rr in m["summary"]["arrays"].items():
                s[k] = _slice(arrays, rr)[0]
            r.summary = s
        return r

    def wave_from(m: dict) -> Wave:
        w = Wave(requests=[req_from(x) for x in m["requests"]],
                 batch=tree_from(TaskArrays, m["batch"]),
                 state=tree_from(PlatformState, m["state"], device=True),
                 bucket=m["bucket"], progress=m["progress"],
                 preemptions=m["preemptions"],
                 waves_waited=m["waves_waited"])
        if m["recs"] is not None:
            w.recs = [tree_from(StepRecord, r) for r in m["recs"]]
        return w

    eng.now = meta["now"]
    eng._order = meta["order"]
    eng.dispatches = meta["dispatches"]
    eng.preemption_count = meta["preemption_count"]
    eng.segments_done = meta["segments_done"]
    eng.svc = meta["svc"]
    eng.base_svc = meta["base_svc"]
    eng.svc_scale = meta["svc_scale"]
    eng.svc_step = eng.svc / eng.cfg.stages
    eng.snapshots_written = meta["snapshots_written"]
    eng.wave_log = [list(w) for w in meta["wave_log"]]
    eng.dead_letter = [dict(d) for d in meta["dead_letter"]]
    eng.pending = [req_from(m) for m in meta["pending"]]
    eng.backlog = [req_from(m) for m in meta["backlog"]]
    eng.preempted = [wave_from(m) for m in meta["preempted"]]
    eng.completed = [req_from(m) for m in meta["completed"]]
    eng._inflight = (wave_from(meta["inflight"])
                     if meta["inflight"] is not None else None)
    eng.alive = np.asarray(meta["alive"], bool)
    eng.health = np.asarray(meta["health"], np.float64)
    eng.core_factor = np.asarray(meta["core_factor"], np.float64)
    eng.fired = [dict(ev) for ev in meta["fired"]]
    eng.pending_faults = [FaultInjection(**f)
                          for f in meta["pending_faults"]]
    eng.detector._last_seen = {int(h): t for h, t
                               in meta["detector_last_seen"].items()}
    eng.detector._times = {int(h): list(ts) for h, ts
                           in meta["detector_times"].items()}
    eng.final_states = {
        int(uid): tuple(_slice(arrays, rr))
        for uid, rr in meta["final_states"].items()}
    if (eng.core_factor != 1.0).any():
        eng.cur_spec = degrade_spec(eng.healthy_spec, eng.core_factor)
    eng._use_masked = (eng._use_masked or bool(eng.fired)
                       or bool(eng.pending_faults))


def serving_digest(eng: QoSPlacementEngine) -> dict:
    """Order-canonical arrays capturing the serving outcome — the
    bit-exactness contract of crash recovery.  Two engines that served
    the same submissions must agree on every entry: completed uids with
    finish/slack, per-request placements and final ``PlatformState``
    (durable engines), shed uids, the wave log, and the virtual clock."""
    comp = sorted(eng.completed, key=lambda r: r.uid)
    flat_log = []
    for w in eng.wave_log:
        flat_log.extend(w)
        flat_log.append(-1)
    out = {
        "completed_uids": np.asarray([r.uid for r in comp], np.int64),
        "finish": np.asarray([r.finish for r in comp], np.float64),
        "slack": np.asarray([r.slack for r in comp], np.float64),
        "shed_uids": np.sort(np.asarray(
            [d["uid"] for d in eng.dead_letter], np.int64)),
        "wave_log": np.asarray(flat_log, np.int64),
        "virtual_time": np.asarray(eng.now, np.float64),
    }
    for r in comp:
        out[f"placements_{r.uid}"] = np.asarray(
            r.summary["placements"], np.int32)
    for uid, st in sorted(getattr(eng, "final_states", {}).items()):
        for fname, a in zip(PlatformState._fields, st):
            out[f"state_{uid}_{fname}"] = np.asarray(a)
    return out


def digests_equal(a: dict, b: dict) -> bool:
    if set(a) != set(b):
        return False
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in a)


# ---------------------------------------------------------------------------
# the durable engine
# ---------------------------------------------------------------------------

class DurableQoSEngine(QoSPlacementEngine):
    """``QoSPlacementEngine`` with snapshots, crash recovery, elastic
    mesh resume, and fault injection with graceful degradation.

    The base wave loop is untouched; durability rides on the four seams
    (``_dispatch_segment`` / ``_charge_segment`` / ``_after_segment`` /
    ``_on_complete``).  With no snapshot dir, no faults and no mesh the
    engine behaves exactly like the base class.
    """

    def __init__(self, platform, params, cfg: QoSConfig = QoSConfig(), *,
                 backlog_scale: float = 1.0,
                 executor: "Callable | str | None" = None,
                 snapshot_dir: Optional[str] = None,
                 snapshot_every: int = 0,       # segments; 0 = off
                 faults: Optional[list] = None,
                 mesh=None,
                 guard: Optional[PreemptionGuard] = None,
                 dead_after_segments: int = 4,
                 trace: bool = False,
                 segment_sleep: float = 0.0,
                 keep: int = 3):
        if cfg.stages > 1:
            raise ValueError(
                "durability does not support pipeline waves (stages > 1): "
                "snapshots and fault-masked executors cover the lockstep "
                "(state)-only checkpoint, not (state, ring)")
        if cfg.continuous:
            raise ValueError(
                "durability does not support continuous batching yet: the "
                "snapshot format packs whole-wave checkpoints, not per-lane "
                "cursors (ROADMAP follow-up)")
        if cfg.measured_svc:
            raise ValueError(
                "durability requires the virtual clock: measured service "
                "times would break bit-exact crash replay")
        super().__init__(platform, params, cfg,
                         backlog_scale=backlog_scale, executor=executor)
        self._stub = executor is not None
        self.snapshot_dir = snapshot_dir
        self.snapshot_every = snapshot_every
        self.saver = (ckpt_lib.AsyncCheckpointer(snapshot_dir, keep=keep)
                      if snapshot_dir else None)
        self.mesh = mesh
        self.guard = guard
        self.trace = trace
        self.segment_sleep = segment_sleep
        self.interrupted = False
        self.healthy_spec = self.spec
        self.cur_spec = self.spec
        n = self.spec.n
        self.alive = np.ones(n, bool)          # scheduler's belief
        self.core_factor = np.ones(n, np.float64)  # execution truth
        self.pending_faults = sorted(faults or [], key=lambda f: f.at_time)
        self.fired: list[dict] = []
        # base_svc / svc_scale / health live on the base engine now
        # (the set_health admission seam); nothing extra to init here
        self.segments_done = 0
        self.snapshots_written = 0
        self.snapshot_time_s = 0.0  # sync time serving loses to pack/save
        self._inflight: Optional[Wave] = None
        self._use_masked = bool(self.pending_faults) or mesh is not None
        # heartbeat detection runs on the serving virtual clock, so the
        # whole fault story is deterministic and replayable
        self.detector = StragglerDetector(
            n, dead_after_s=dead_after_segments * cfg.chunk * self.svc,
            clock=lambda: self.now)
        self.final_states: dict[int, tuple] = {}

    # ---- fault machinery ------------------------------------------------

    def _fire_due_faults(self) -> None:
        while (self.pending_faults
               and self.pending_faults[0].at_time <= self.now):
            f = self.pending_faults.pop(0)
            self.core_factor[f.core] *= f.factor
            self.cur_spec = degrade_spec(self.healthy_spec,
                                         self.core_factor)
            self.fired.append({
                "at_time": f.at_time, "core": f.core, "factor": f.factor,
                "handled": f.handled, "fired_at": self.now,
                "detected_at": None})
            if self.trace:
                print(f"FAULT core={f.core} factor={f.factor} "
                      f"at={self.now:.4f} handled={f.handled}", flush=True)

    def _heartbeat_and_detect(self) -> None:
        seg_cost = self.cfg.chunk * self.svc
        for core in range(self.spec.n):
            f = self.core_factor[core]
            if f == 1.0:
                self.detector.record(HeartbeatRecord(
                    core, self.segments_done, seg_cost, self.now))
            elif f < DEAD_CORE_FACTOR:
                # a throttled core still makes progress: it heartbeats,
                # but its step time is inflated by the degradation — the
                # detector's threshold (straggler) arm fires instead of
                # waiting out the dead-host timeout
                self.detector.record(HeartbeatRecord(
                    core, self.segments_done, seg_cost * f, self.now))
            # else: a dead core goes silent -> dead_hosts() after timeout
        dead = set(self.detector.dead_hosts())
        slow = set(self.detector.stragglers())
        for ev in self.fired:
            if ev["detected_at"] is not None:
                continue
            core = ev["core"]
            if core in dead:
                ev["detected_at"] = self.now
                if self.trace:
                    print(f"DETECTED core={core} at={self.now:.4f}",
                          flush=True)
                if ev["handled"]:
                    self._mitigate(core)
            elif core in slow and 1.0 < self.core_factor[core]:
                ev["detected_at"] = self.now
                if self.trace:
                    print(f"STRAGGLER core={core} at={self.now:.4f}",
                          flush=True)
                if ev["handled"]:
                    self._mitigate_degraded(core, self.core_factor[core])

    def _mitigate(self, core: int) -> None:
        """Dead-core mitigation: drop the core from the placement argmax
        and shrink admission capacity through the shared ``set_health``
        seam — shedding then naturally drops what no longer fits."""
        self.alive[core] = False
        h = np.array(self.health, np.float64)
        h[core] = 0.0
        self.set_health(h)
        if self.trace:
            print(f"MITIGATE core={core} svc_scale={self.svc_scale:.4f}",
                  flush=True)

    def _mitigate_degraded(self, core: int, factor: float) -> None:
        """Straggler mitigation: the core stays in the placement argmax
        (it still makes progress) but admission sees its shrunken
        capacity, so the stretched service cost sheds marginal routes
        instead of letting the slow core turn them into deadline misses."""
        h = np.array(self.health, np.float64)
        h[core] = min(h[core], 1.0 / max(float(factor), 1.0))
        self.set_health(h)
        if self.trace:
            print(f"MITIGATE-DEGRADED core={core} health={h[core]:.3f} "
                  f"svc_scale={self.svc_scale:.4f}", flush=True)

    # ---- durability seams ----------------------------------------------

    def _segments_per_call(self, wave: Wave) -> int:
        """One: the snapshot cadence, fault firing and the masked
        executor act at every segment boundary."""
        return 1

    def _dispatch_segment(self, wave: Wave, seg: TaskArrays):
        self._fire_due_faults()
        if self._stub or not self._use_masked:
            return super()._dispatch_segment(wave, seg)
        fn = _masked_segment_fn(self.cur_spec, self.backlog_scale,
                                mesh=self.mesh)
        return self._call_lanes(fn, seg, wave.state,
                                jnp.asarray(self.alive))

    def _charge_segment(self, wave: Wave, recs) -> None:
        cost = self.cfg.chunk * self.svc
        if self.saver is not None and not self._stub and wave.recs:
            # normalize this segment's transitions to host eagerly: wave
            # completion pays this transfer anyway, and paying it here —
            # one segment at a time — means a snapshot packs plain numpy
            # instead of blocking on a backlog of device recs
            recs = jax.device_get(recs)
            wave.recs[-1] = recs
        if self.fired and not self._stub:
            # honest lockstep cost: accelerator-seconds actually consumed
            # over what the healthy platform would have spent on the same
            # placements — work landing on a degraded core slows its
            # whole lockstep wave by the degradation factor
            r = jax.device_get(recs)
            v = np.asarray(r.valid, bool)
            if v.any():
                act = np.asarray(r.action)[v]
                ex = np.asarray(r.exec_time, np.float64)[v]
                healthy = (ex / self.core_factor[act]).sum()
                if healthy > 0.0:
                    cost *= max(float(ex.sum() / healthy), 1.0)
        self.now += cost

    def _after_segment(self, wave: Wave) -> None:
        self.segments_done += 1
        self._heartbeat_and_detect()
        if self.segment_sleep:
            time.sleep(self.segment_sleep)
        if self.trace:
            print(f"SEG {self.segments_done} now={self.now:.4f} "
                  f"progress={wave.progress}/{wave.bucket}", flush=True)
        due = (self.saver is not None and self.snapshot_every > 0
               and self.segments_done % self.snapshot_every == 0)
        stop = self.guard is not None and self.guard.preempted
        if due or stop:
            self.snapshot(inflight=wave)
        if stop:
            if self.saver is not None:
                self.saver.wait()
            self.interrupted = True
            self._halt = True

    def _on_complete(self, req: RouteRequest, lane_final,
                     lane_recs) -> None:
        self.final_states[req.uid] = tuple(
            np.asarray(x) for x in lane_final)

    # ---- snapshot / restore --------------------------------------------

    def snapshot(self, inflight: Optional[Wave] = None) -> None:
        if self.saver is None:
            return
        # the step is a dedicated monotonic counter (not segments_done):
        # it is packed into the snapshot, so a restored engine keeps
        # counting where the crashed one stopped and its snapshots never
        # collide with — or sort below — the survivors on disk
        t0 = time.perf_counter()
        self.snapshots_written += 1
        # pack + encode synchronously: a consistent cut of the serving
        # state (the meta freezes live containers like wave_log, the
        # blob copies every array) — only the disk write is async.
        # Deferring the device transfers to the writer thread measures
        # worse, not better: hundreds of background device_gets contend
        # with serving's own dispatches on the GIL and the jax runtime.
        with self._tracer.span("snapshot"):
            arrays, meta = pack_engine(self, inflight=inflight)
            self.saver.save(self.snapshots_written,
                            encode_snapshot(arrays, meta))
        self.snapshot_time_s += time.perf_counter() - t0
        if self.trace:
            print(f"SNAPSHOT step={self.segments_done} "
                  f"now={self.now:.4f}", flush=True)

    @classmethod
    def from_packed(cls, arrays: list, meta: dict, platform, *,
                    backlog_scale: float = 1.0, executor=None, mesh=None,
                    guard=None, snapshot_dir=None, snapshot_every=None,
                    trace=False, segment_sleep=0.0) -> "DurableQoSEngine":
        params = DQNParams(*[jnp.asarray(x)
                             for x in _slice(arrays, meta["params"])])
        eng = cls(platform, params, QoSConfig(**meta["cfg"]),
                  backlog_scale=backlog_scale, executor=executor,
                  snapshot_dir=snapshot_dir,
                  snapshot_every=(meta["snapshot_every"]
                                  if snapshot_every is None
                                  else snapshot_every),
                  mesh=mesh, guard=guard, trace=trace,
                  segment_sleep=segment_sleep)
        snap_et = _slice(arrays, meta["exec_time"])[0]
        if not np.array_equal(np.asarray(eng.healthy_spec.exec_time),
                              snap_et):
            raise ValueError(
                "snapshot was taken on a different platform "
                "(exec-time tables disagree)")
        unpack_into(eng, arrays, meta)
        return eng

    @classmethod
    def restore(cls, snapshot_dir: str, platform,
                **kwargs) -> "DurableQoSEngine":
        """Rebuild the engine from the latest snapshot in
        ``snapshot_dir`` (or an explicit ``path=``).  The snapshot is
        self-describing; ``platform`` only provides the spec tables,
        which are integrity-checked against the snapshot."""
        path = kwargs.pop("path", None) \
            or ckpt_lib.latest_checkpoint(snapshot_dir)
        if path is None:
            raise FileNotFoundError(
                f"no snapshot under {snapshot_dir!r}")
        _, leaves, _ = ckpt_lib.load_checkpoint_arrays(path)
        arrays, meta = decode_snapshot(leaves)
        if meta["version"] != SNAPSHOT_VERSION:
            raise ValueError(f"snapshot version {meta['version']} != "
                             f"{SNAPSHOT_VERSION}")
        kwargs.setdefault("snapshot_dir", snapshot_dir)
        return cls.from_packed(arrays, meta, platform, **kwargs)

    # ---- serving loop --------------------------------------------------

    def _resume_inflight(self) -> None:
        """Continue the wave that was mid-``_run_wave`` at snapshot time.
        The snapshot is taken inside ``_after_segment``, i.e. *before*
        the loop's preemption check — so replay re-applies that check on
        the restored state (a pure function of clock + queues, hence the
        same verdict the uninterrupted run reached) before serving on."""
        w, self._inflight = self._inflight, None
        if w.progress < w.bucket and self._preempt_if_due(w):
            return
        self._run_wave(w)

    def run_until_done(self, max_waves: int = 100_000) -> None:
        if self._inflight is not None:
            self._resume_inflight()
        super().run_until_done(max_waves)

    def serve_waves(self, k: int) -> int:
        """Serve up to ``k`` admission rounds — the crash-point control
        of the recovery tests and benchmark.  Returns rounds served."""
        served = 0
        if self._inflight is not None and k > 0:
            self._resume_inflight()
            served += 1
        while served < k and not self._halt:
            wave = self._next_wave()
            if wave is None:
                break
            self._run_wave(wave)
            served += 1
        return served

    def stats(self) -> dict:
        s = super().stats()
        s.update({
            "snapshots_written": self.snapshots_written,
            "snapshot_time_s": self.snapshot_time_s,
            "segments_done": self.segments_done,
            "faults_fired": len(self.fired),
            "cores_masked": int((~self.alive).sum()),
            "svc_scale": self.svc_scale,
            "interrupted": self.interrupted,
        })
        return s
