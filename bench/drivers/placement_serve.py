"""Driver of FlexAI placement serving: the program's ``QoSPlacementEngine``.

Set-up builds the engine as the program's serving launcher does
(``launch/serve.run_qos_placement_serving``): the HMAI platform of the
configuration, the Q-net weights (made here, on the device, in one
jitted call from the seed), FIFO admission with the configured slots,
bucket floor and segment length.  It warms the one device shape the
window uses — a ``[slots, chunk]`` segment — with one short wave through
a second engine, and queues the closed backlog where the mix has one.

The window then drives the engine through its public surface
(``submit``, ``run_until_done``, ``stats``).  Two hooks of the engine
serve the harness: ``_after_segment`` marks each segment boundary on the
host clock, and ``_on_complete`` records each request's placements as
they reach the host, replaces a closed backlog's completed window, and
closes the window at the first wave boundary at or after its length at
which the waves served in it number a multiple of the mix's
``close_waves``.

Once the window has closed and the program's state is freed, every
request it completed is replayed through the configuration's plain
reference.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np


def qnet_widths(config: dict) -> list:
    q = config["qnet"]
    return [q["state_dim"], *q["hidden"], q["n_actions"]]


def make_params(seed: int, widths) -> tuple:
    """Glorot-uniform weights and zero biases, as the program's Q-net
    initialiser draws them, made on the device in one jitted call.  The
    seed may exceed 32 bits: its high word is folded into the key."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def init(lo, hi):
        key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
        out = []
        for k, (a, b) in zip(jax.random.split(key, len(widths) - 1),
                             zip(widths[:-1], widths[1:])):
            lim = jnp.sqrt(6.0 / (a + b))
            out += [jax.random.uniform(k, (a, b), jnp.float32, -lim, lim),
                    jnp.zeros((b,), jnp.float32)]
        return tuple(out)

    return init(np.uint32(seed & 0xFFFFFFFF),
                np.uint32((seed >> 32) & 0xFFFFFFFF))


@dataclasses.dataclass
class Served:
    """A request whose placements reached the host."""
    window: int           # index into the mix's windows
    n_tasks: int
    t_done: float         # host clock
    actions: np.ndarray   # [bucket] placements, padding included
    met: np.ndarray       # [bucket] deadline verdicts
    valid: np.ndarray     # [bucket] record validity
    placements: object    # the request summary's placements, as served


def engine_class():
    from repro.serve.qos import QoSPlacementEngine

    class BenchEngine(QoSPlacementEngine):
        """The engine, with the harness's marks in its two hooks."""

        def bench_init(self, timeline, on_done):
            self.bench_tl = timeline
            self.bench_on_done = on_done
            self.bench_end = float("inf")
            self.bench_close_waves = 1
            self.bench_waves = 0             # waves drained since start
            self.bench_served: dict = {}
            self.bench_window: dict = {}     # uid -> window index

        def _after_segment(self, wave):
            self.bench_tl.mark("dispatch" if wave.progress > self.cfg.chunk
                               else "admission")

        def _on_complete(self, req, lane_final, lane_recs):
            last = self.bench_tl.marks[-1][1]
            first_of_wave = last in ("dispatch", "admission")
            t = self.bench_tl.mark("drain" if first_of_wave
                                   else "complete")
            self.bench_waves += first_of_wave
            self.bench_served[req.uid] = Served(
                window=self.bench_window[req.uid], n_tasks=req.n_tasks,
                t_done=t, actions=np.array(lane_recs.action),
                met=np.array(lane_recs.met), valid=np.array(lane_recs.valid),
                placements=req.summary["placements"])
            if (t >= self.bench_end
                    and self.bench_waves % self.bench_close_waves == 0):
                self._halt = True
            self.bench_on_done(req)

    return BenchEngine


def _task_arrays(w):
    from repro.core.tasks import TaskArrays
    return TaskArrays(kind=w.kind, arrival=w.arrival, safety=w.safety,
                      group=w.group, valid=np.ones(w.n, bool))


def _warm(plat, params, qcfg, backlog_scale, windows):
    """One wave of the smallest bucket through a second engine: compiles
    (or loads) the ``[slots, chunk]`` segment and the engine's small
    per-wave device operations, none of which depends on the bucket."""
    from repro.serve.qos import QoSPlacementEngine
    from repro.core.tasks import TaskArrays
    eng = QoSPlacementEngine(plat, params, qcfg, backlog_scale=backlog_scale)
    m = qcfg.min_bucket
    for w in windows[: qcfg.slots]:
        ta = _task_arrays(w)
        eng.submit(TaskArrays(*[np.asarray(a)[:m] for a in ta]))
    eng.run_until_done()


def run(r) -> dict:
    """Run one cell.  ``r`` carries ``config``, ``mix``, ``seed``,
    ``seconds``, ``trace_s`` (0: untraced), ``trace_dir``, ``timeline``,
    ``compiles``, ``reference``, ``control``, ``t_proc0`` and
    ``read_memory``."""
    import jax

    from benchlib import trace as tracing
    from benchlib.spans import SERVING
    from benchlib.traffic import release_plan
    from repro.core.flexai.dqn import DQNParams
    from repro.core.hmai import HMAIPlatform
    from repro.serve.qos import QoSConfig

    cfg, mix, tl = r.config, r.mix, r.timeline
    ecfg = cfg["engine"]
    windows = mix.windows()
    arrays = [_task_arrays(w) for w in windows]
    plat = HMAIPlatform(capacity_scale=cfg["platform"]["capacity_scale"])
    params = DQNParams(*make_params(r.seed, qnet_widths(cfg)))
    qcfg = QoSConfig(policy=ecfg["policy"], slots=ecfg["slots"],
                     min_bucket=ecfg["min_bucket"], chunk=ecfg["chunk"])
    backlog_scale = ecfg["backlog_scale"]
    _warm(plat, params, qcfg, backlog_scale, windows)

    eng = engine_class()(plat, params, qcfg, backlog_scale=backlog_scale)
    due: dict = {}               # uid -> due time (open loop)
    cursor = [0]

    def submit(i):
        req = eng.submit(arrays[i % len(arrays)], arrival=eng.now)
        eng.bench_window[req.uid] = i % len(arrays)
        return req

    closed = mix.arrivals == "closed_backlog"
    eng.bench_init(tl, (lambda req: submit_next()) if closed
                   else (lambda req: None))

    def submit_next():
        submit(cursor[0])
        cursor[0] += 1

    if closed:
        for _ in range(len(arrays)):
            submit_next()
    jax.block_until_ready(params)
    out = {"slots": qcfg.slots, "chunk": qcfg.chunk}

    def serve_closed(seconds):
        eng._halt = False
        eng.bench_waves = 0
        eng.bench_close_waves = mix.close_waves
        eng.bench_end = time.perf_counter() + seconds
        tl.mark("harness")
        eng.run_until_done()
        tl.mark("admission")

    next_req = [0]

    def serve_open(seconds):
        """Release each request at its due time, the end of its window
        of driving counted from the start of the first request's window;
        serve whatever is queued between releases."""
        t_base = time.perf_counter()
        plan = release_plan(windows, next_req[0], seconds, mix.window_s)
        late = []
        tl.mark("harness")
        i = 0
        while True:
            now = time.perf_counter()
            while i < len(plan) and t_base + plan[i] <= now:
                d = t_base + plan[i]
                due[submit(next_req[0]).uid] = d
                late.append(now - d)
                next_req[0] += 1
                i += 1
            tl.mark("submit")
            if eng.backlog:
                eng.run_until_done()
                tl.mark("admission")
            elif i < len(plan):
                time.sleep(max(t_base + plan[i] - time.perf_counter(), 0.0))
                tl.mark("wait_due")
            else:
                break
        return late

    serve = serve_closed if closed else serve_open
    gc.collect()
    setup_done = tl.mark("setup")
    out["setup_s"] = setup_done - r.t_proc0
    d0 = eng.dispatches
    r.compiles.arm()
    t_start = time.perf_counter()
    late = serve(r.seconds)
    t_close = time.perf_counter()
    out["compiles_in_window"] = r.compiles.disarm()
    served = list(eng.bench_served.values())
    in_window = [s for s in served if s.t_done <= t_close]
    out["window_s"] = t_close - t_start
    out["dispatches"] = eng.dispatches - d0
    out["waves"] = eng.bench_waves
    out["decisions"] = sum(s.n_tasks for s in in_window)
    out["serve_s"] = tl.seconds(SERVING, t_start, t_close)
    out["requests"] = len(in_window)
    starts = [t for t, label in tl.marks
              if label == "admission" and t_start <= t <= t_close]
    waves = np.diff(starts) if len(starts) > 1 else np.zeros(1)
    out["wave_s"] = [len(waves), float(waves.min()),
                     float(np.median(waves)), float(waves.max())]
    out["waves_ms"] = [round(float(w) * 1e3, 1) for w in waves]
    if not closed:
        lat = [(eng.bench_served[u].t_done - d) * 1e3
               for u, d in due.items() if u in eng.bench_served]
        out["latencies_ms"] = lat
        out["generator_late_ms"] = max(late) * 1e3 if late else 0.0
        out["attempted"] = len(due)
        out["failed"] = len(due) - len(lat)
    else:
        out["attempted"] = len(in_window)
        out["failed"] = 0
    if r.trace_s:
        d1 = eng.dispatches
        out["trace"] = tracing.capture(str(r.trace_dir),
                                       lambda: serve(r.trace_s), tl)
        out["trace"].pop("op_s")
        out["trace"]["dispatches"] = eng.dispatches - d1
    out["memory_peak_bytes"] = r.read_memory()

    # the program's state goes before the reference runs
    served = list(eng.bench_served.values())
    params_host = [np.asarray(p) for p in jax.device_get(tuple(params))]
    del eng, params
    gc.collect()
    out.update(check(served, windows, params_host, cfg, r.reference,
                     r.control))
    return out


def check(served, windows, params, config, reference, control=False):
    """Replay every served request through the plain reference.

    Numbers compared:

    * ``q_gap``: the widest gap by which a served placement's Q value
      lies below the reference's best, in the reference's float64;
    * ``verdict_mismatch``: tasks whose deadline verdict differs from the
      reference's float32 clock on the same placements;
    * ``drain_errors``: requests whose records came back misaligned —
      a length other than the window's, padding marked valid or a real
      task marked padding, a placement outside the platform, or a
      request summary whose placements are not the records' placements
      of its own tasks, element for element."""
    tab = reference.tables(config)
    n_acc = tab["n"]
    R = len(served)
    T = max((s.actions.shape[0] for s in served), default=1)
    kind = np.zeros((R, T), np.int32)
    arrival = np.zeros((R, T), np.float32)
    safety = np.ones((R, T), np.float32)
    acts = np.zeros((R, T), np.int64)
    met = np.zeros((R, T), bool)
    n_tasks = np.zeros(R, np.int64)
    drain_errors = 0
    for i, s in enumerate(served):
        w = windows[s.window]
        n = w.n
        b = s.actions.shape[0]
        served_pl = np.asarray(s.placements)
        ok = (s.n_tasks == n and served_pl.shape == (n,) and b >= n
              and bool((served_pl == s.actions[:n]).all())
              and bool(s.valid[:n].all()) and not bool(s.valid[n:].any())
              and bool(((s.actions[:n] >= 0)
                        & (s.actions[:n] < n_acc)).all()))
        drain_errors += not ok
        m = min(n, b)
        n_tasks[i] = m
        kind[i, :m] = w.kind[:m]
        arrival[i, :m] = w.arrival[:m]
        safety[i, :m] = w.safety[:m]
        acts[i, :m] = np.clip(s.actions[:m], 0, n_acc - 1)
        met[i, :m] = s.met[:m]
    t0 = time.perf_counter()
    ref = reference.replay(tab, params, kind, arrival, safety, n_tasks,
                           acts, control=control)
    live = np.arange(T)[None, :] < n_tasks[:, None]
    out = {
        "checks": {
            "q_gap": float(ref["gap"][live].max()) if live.any() else 0.0,
            "verdict_mismatch": int((ref["met32"] != met)[live].sum()),
            "drain_errors": int(drain_errors),
        },
        "checked_requests": R,
        "checked_tasks": int(live.sum()),
        "q_flips": int((ref["gap"][live] > 0).sum()),
        "q_gap_position": int(np.unravel_index(
            np.argmax(np.where(live, ref["gap"], -1.0)), live.shape)[1]),
        "reference_s": time.perf_counter() - t0,
    }
    if control:
        out["control"] = {
            "q_gap": float(ref["ctrl_gap"][live].max()),
            "verdict_mismatch": int((ref["ctrl_met"] != ref["met32"])[live]
                                    .sum()),
            "drain_errors": 0,
        }
        out["control_q_flips"] = int((ref["ctrl_gap"][live] > 0).sum())
    return out
