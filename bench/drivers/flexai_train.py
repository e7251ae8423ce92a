"""Driver of FlexAI policy training: the program's fused ``ScanFlexAI``.

Set-up builds the trainer with the training launcher's own
``launch/train.build_flexai_trainer``, as ``--flexai`` calls it (one
lane, the TD-update kernel as the configuration states, the routes'
camera-rate factor as the platform's capacity, weights from the seed)
and refuses to run when the configuration file states another trainer,
Q-net or platform than the one built.  The mix's routes come from the
benchmark's generator, each cut to its first ``episode_tasks`` tasks,
with the held-out eval route ``eval_route_seed`` in the first vehicle's
area (the mix's ``window_s`` is not read: an episode is cut by task
count).  One warm-up episode through ``ScanFlexAI.train`` and one greedy
eval compile the two device programs; training then continues from the
warmed state.

The window drives ``ScanFlexAI.train``'s own loop — routes cycled, a
greedy eval on the held-out route every ``eval_every`` episodes — and
its ``on_episode`` hook closes the window at the first episode boundary
at or after the window's length.  The hook keeps, for the first and the
last episode of the window, the trainer's state before it (the jitted
episode donates nothing, so it stays valid on the device) and the
episode's records, losses and update mask as the trainer fetched them.
``decisions`` counts the valid tasks of the window's episodes: each is
one placement decision the agent acted on and learned from.

A ``--trace 1`` run then profiles one more whole episode, with a
``repro.serve.tracing.Tracer`` attached to the trainer.

Once the windows have closed, the program trains again from the same
state, as episodes of their own, the first ``learning_check_tasks``
tasks of each kept episode and its tasks up to its first TargNet sync;
then it is freed, and the plain reference trains each kept episode and
its prefix from the program's own starting state, teacher-forced with
the program's actions, and compares (:func:`check_episode`).

The run seed sets the trainer's ``FlexAIConfig.seed``; its PRNG key
keeps the seed's low 32 bits.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
import time

import numpy as np

KERNEL = "dqn_td_update"         # the TD kernel's pallas_call name
BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Episode:
    """One trained episode of the window, as the program left it."""
    index: int            # the global episode number
    before: object        # TrainState before the episode (device)
    outputs: tuple        # (records, losses, update_mask) on the host


def episode_tasks(route: dict, n: int):
    """The first ``n`` tasks of a generated route as ``TaskArrays``."""
    from repro.core.tasks import TaskArrays
    if route["kind"].size < n:
        raise ValueError(f"a route of {route['kind'].size} tasks cannot "
                         f"give an episode of {n}")
    return TaskArrays(kind=route["kind"][:n],
                      arrival=route["arrival"][:n].astype(np.float32),
                      safety=route["safety"][:n], group=route["group"][:n],
                      valid=np.ones(n, bool))


def _verify(trainer, config: dict, mix, reference) -> None:
    """The configuration file states the trainer that was built."""
    want = dict(config["trainer"])
    built = dataclasses.asdict(trainer.cfg)
    built.pop("seed")
    built.update(lanes=trainer.lanes, td_kernel=trainer.td_kernel)
    if built != want:
        raise ValueError(f"the configuration states trainer {want}, the "
                         f"launcher builds {built}")
    q = config["qnet"]
    widths = [q["state_dim"], *q["hidden"], q["n_actions"]]
    shapes = [tuple(p.shape) for p in trainer.ts.eval_p]
    if shapes[::2] != list(zip(widths[:-1], widths[1:])):
        raise ValueError(f"the configuration states Q-net {widths}, the "
                         f"trainer holds {shapes}")
    if mix.rate_scale != config["platform"]["capacity_scale"]:
        raise ValueError("the launcher scales the platform's capacity by "
                         "the routes' rate scale; the configuration and "
                         "the mix disagree")
    tab = reference.tables(config)
    for name, table in (("exec", trainer.spec.exec_time),
                        ("energy", trainer.spec.energy)):
        if not np.array_equal(tab[name], np.asarray(table)):
            raise ValueError(f"the configuration's {name} table is not "
                             f"the program's platform")


def run(r) -> dict:
    """Run one cell.  ``r`` carries ``config``, ``mix``, ``seed``,
    ``seconds``, ``trace_s`` (0: untraced), ``trace_dir``, ``timeline``,
    ``compiles``, ``reference``, ``control``, ``t_proc0`` and
    ``read_memory``."""
    # imported first, so that a program without it fails at once
    from repro.launch.train import build_flexai_trainer

    import jax

    from benchlib import train_trace
    from benchlib.traffic import build_route
    from repro.serve.tracing import Tracer

    cfg, mix, tl = r.config, r.mix, r.timeline
    extra = json.loads((BENCH / "traffic" / f"{mix.name}.json").read_text())
    n_tasks = int(extra["episode_tasks"])
    eval_every = int(extra["eval_every"])
    routes = [episode_tasks(build_route(area, seed, mix.route,
                                        mix.rate_scale), n_tasks)
              for area, seed in mix.vehicles]
    held_out = episode_tasks(build_route(
        mix.vehicles[0][0], int(extra["eval_route_seed"]), mix.route,
        mix.rate_scale), n_tasks)
    trainer = build_flexai_trainer(
        seed=r.seed, rate_scale=mix.rate_scale,
        lanes=cfg["trainer"]["lanes"], td_kernel=cfg["trainer"]["td_kernel"])
    _verify(trainer, cfg, mix, r.reference)

    trainer.train(routes, episodes=1)          # episode 0
    trainer._eval_stms(held_out)
    jax.block_until_ready(trainer.ts)
    cursor = {"episode": 1}

    def train(seconds):
        """Train whole episodes until the first boundary at or after
        ``seconds``; return the window's episodes' counts, its first and
        last episode and its end."""
        got = {"episodes": 0, "decisions": 0, "td_updates": 0,
               "evals": 0, "first": None, "last": None, "t_close": None}
        before = [trainer.ts]
        t_end = time.perf_counter() + seconds

        def on_episode(ep, tr):
            t = tl.mark("train")
            recs, losses, upd = tr.last_episode
            got["episodes"] += 1
            got["decisions"] += int(recs.valid.sum())
            got["td_updates"] += int(upd.sum())
            got["evals"] += (ep + 1) % eval_every == 0
            e = Episode(ep, before[0], tr.last_episode)
            got["first"] = got["first"] or e
            got["last"] = e
            before[0] = tr.ts
            cursor["episode"] = ep + 1
            if t >= t_end:
                got["t_close"] = t
                r.compiles.disarm()
                return True
            return None

        tl.mark("harness")
        trainer.train(routes, episodes=2**62, eval_queue=held_out,
                      eval_every=eval_every, on_episode=on_episode,
                      start_episode=cursor["episode"])
        tl.mark("restore")
        return got

    gc.collect()
    setup_done = tl.mark("setup")
    out = {"setup_s": setup_done - r.t_proc0, "episode_tasks": n_tasks}
    r.compiles.arm()
    t_start = time.perf_counter()
    got = train(r.seconds)
    out["compiles_in_window"] = r.compiles.count
    out["window_s"] = got["t_close"] - t_start
    for k in ("episodes", "decisions", "td_updates", "evals"):
        out[k] = got[k]
    out["attempted"] = got["episodes"]
    # the window closes on an episode boundary: no episode is left open
    out["failed"] = 0
    checked = [got["first"]] + ([got["last"]]
                                if got["last"] is not got["first"] else [])

    if r.trace_s:
        tracer = Tracer()
        traced = {}

        def train_traced():
            # one whole episode: the TPU profiler keeps about 6.29
            # million operation events, and an episode holds 3.57 million
            trainer.tracer = tracer
            traced["lo"] = time.perf_counter_ns()
            train(0.0)
            traced["hi"] = time.perf_counter_ns()
            trainer.tracer = None

        t0 = time.perf_counter()
        out["trace"] = train_trace.capture(str(r.trace_dir), train_traced,
                                           tl, KERNEL)
        out["trace_capture_s"] = time.perf_counter() - t0
        out["spans"] = tracer.summary(traced["lo"], traced["hi"])
        out["kernel_ops"] = out["trace"]["kernel_ops"]
    out["memory_peak_bytes"] = r.read_memory()

    # the program trains each checked episode's learning prefix, and
    # its prefix up to its first TargNet sync, again from the same
    # state (the compiled episode, its tail masked); then it goes
    # before the reference runs
    prefix = int(cfg["learning_check_tasks"])
    sync_every = int(cfg["trainer"]["target_sync_every"])
    held = []

    def train_prefix(before, tasks, n):
        trainer.ts = before
        trainer.train_episode(tasks._replace(
            valid=np.arange(tasks.valid.size) < n))
        return jax.device_get(trainer.ts)

    for e in checked:
        tasks = routes[e.index % len(routes)]
        params = list(train_prefix(e.before, tasks, prefix).eval_p)
        n_sync = first_sync(e.outputs[2], int(e.before.updates), sync_every)
        synced = None
        if n_sync is not None:
            ts = train_prefix(e.before, tasks, n_sync)
            synced = all(np.array_equal(t, v)
                         for t, v in zip(ts.targ_p, ts.eval_p))
        held.append(Held(e.index, jax.device_get(e.before), e.outputs,
                         params, synced))
    del trainer, checked, got
    gc.collect()
    out.update(check(held, routes, cfg, r.reference, r.control))
    return out


@dataclasses.dataclass
class Held:
    """A checked episode on the host: the program's state before it, its
    outputs, the EvalNet after the program trained its learning prefix,
    and whether its TargNet equalled its EvalNet after the prefix that
    ends with the episode's first sync (None: no sync in the episode)."""
    index: int
    before: object
    outputs: tuple
    prefix_params: list
    synced: bool | None = None


def first_sync(update_mask, updates: int, sync_every: int):
    """The number of tasks of an episode up to and including its first
    TargNet sync: the update that brings the trainer's update count to a
    multiple of ``sync_every`` (None: the episode makes none)."""
    count = updates + np.cumsum(np.asarray(update_mask, np.int64))
    at = np.nonzero(np.asarray(update_mask, bool)
                    & (count % sync_every == 0))[0]
    return int(at[0]) + 1 if at.size else None


def start_state(ts) -> dict:
    """A host ``TrainState`` as the reference's plain arrays (the replay
    ring without its trash row)."""
    cap = ts.replay.s.shape[0] - 1
    rp = ts.replay
    return {
        "eval_p": list(ts.eval_p), "targ_p": list(ts.targ_p),
        "mu": list(ts.opt.mu), "nu": list(ts.opt.nu),
        "opt_step": int(ts.opt.step),
        "ring": {"s": rp.s[:cap], "a": rp.a[:cap], "r": rp.r[:cap],
                 "s_next": rp.s_next[:cap], "done": rp.done[:cap],
                 "ptr": int(rp.ptr), "size": int(rp.size)},
        "env_steps": int(ts.env_steps), "updates": int(ts.updates),
        "key": np.asarray(ts.key)}


def _rel(a, b) -> np.ndarray:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(np.maximum(np.abs(a), np.abs(b)),
                                      1e-30)


def _plain(tasks, n=None) -> dict:
    n = tasks.kind.size if n is None else n
    return {"kind": tasks.kind[:n], "arrival": tasks.arrival[:n],
            "safety": tasks.safety[:n],
            "valid": np.asarray(tasks.valid[:n], bool)}


def _judge(whole, prefix, actions, met, losses, update, params) -> dict:
    """The numbers of one episode, of a run whose actions, verdicts,
    per-step losses and update mask over the episode are given and whose
    EvalNet after the prefix is ``params``, against the reference's run
    of the whole episode (``whole``) and of its prefix (``prefix``)."""
    n = prefix["loss"].shape[0]
    explore = whole["explore"]
    q = np.asarray(prefix["q"], np.float64)
    gap = q.max(axis=1) - q[np.arange(n), actions[:n]]
    greedy = ~prefix["explore"]
    # the prefix's last transition is terminal in the prefix, not in
    # the episode: its update is compared through the parameters only
    either = (prefix["update"] | update[:n])[: n - 1]
    return {
        "random_action_mismatch": int(
            (explore & (actions != whole["random_action"])).sum()),
        "verdict_mismatch": int((met != whole["met"]).sum()),
        "q_gap": float(gap[greedy].max()) if greedy.any() else 0.0,
        "loss_rel_err": float(_rel(losses[: n - 1], prefix["loss"][: n - 1])
                              [either].max()) if either.any() else 0.0,
        "param_rel_err": max(
            float(np.linalg.norm(np.asarray(p, np.float64) - rp)
                  / max(np.linalg.norm(np.asarray(rp, np.float64)), 1e-30))
            for p, rp in zip(params, prefix["eval_p"])),
    }


def check_episode(tab, trainer: dict, start: dict, tasks, outputs,
                  prefix: int, prefix_params, reference,
                  control: bool = False) -> dict:
    """Train one episode again through the plain reference from the
    program's ``start`` with the program's actions, and compare.

    DQN training amplifies float rounding: two correct trainers of the
    same episode drift apart over thousands of updates.  So the learning
    is compared over the episode's first ``prefix`` tasks, which the
    program (``prefix_params``: its EvalNet after training that prefix
    as an episode of its own from ``start``) and the reference both
    train; the draws and the clock over the whole episode.

    Numbers compared (``checks``):

    * ``random_action_mismatch``: exploring steps of the episode (by the
      reference's epsilon draw) whose action is not the reference's
      random draw;
    * ``verdict_mismatch``: deadline verdicts of the episode's placements
      that differ from the reference's float32 clock;
    * ``q_gap``: at the prefix's greedy steps, how far the action's Q
      under the reference's parameters lies below the reference's best;
    * ``loss_rel_err``: the largest relative gap between the per-update
      losses of the prefix, over the steps at which either side updated
      (a step only one side updates at reads 1);
    * ``param_rel_err``: the largest norm-wise relative gap of an
      EvalNet parameter array after the prefix.

    :func:`check` adds ``sync_mismatch``: checked episodes whose TargNet
    was not the EvalNet, bit for bit, after the prefix that ends with
    their first TargNet sync (the reference's in the control's place).

    With ``control``, the same numbers of the reference one step below
    the stated precision, in the program's place, are under
    ``control``."""
    recs, losses, upd = outputs
    actions = np.asarray(recs.action, np.int64)
    whole = reference.replay(tab, trainer, start, _plain(tasks), actions)
    pre = reference.replay(tab, trainer, start, _plain(tasks, prefix),
                           actions[:prefix])
    out = {"checks": _judge(whole, pre, actions, np.asarray(recs.met, bool),
                            np.asarray(losses), np.asarray(upd, bool),
                            prefix_params),
           "update_steps": int(np.asarray(upd).sum()),
           "explore_steps": int(whole["explore"].sum())}
    if control:
        low_whole = reference.replay(tab, trainer, start, _plain(tasks),
                                     actions, lowp=True)
        low = reference.replay(tab, trainer, start, _plain(tasks, prefix),
                               actions[:prefix], lowp=True)
        # in the program's place: its own draws, its own greedy argmax
        low_actions = np.where(low_whole["explore"],
                               low_whole["random_action"], actions)
        low_actions[:prefix] = np.where(low["explore"],
                                        low["random_action"],
                                        np.argmax(low["q"], axis=1))
        losses_low = np.zeros_like(np.asarray(losses))
        losses_low[:prefix] = low["loss"]
        upd_low = np.zeros_like(np.asarray(upd, bool))
        upd_low[:prefix] = low["update"]
        out["control"] = _judge(whole, pre, low_actions, low_whole["met16"],
                                losses_low, upd_low, low["eval_p"])
    return out


def check(held, routes, config, reference, control=False) -> dict:
    """Judge each :class:`Held` episode: each count summed over them,
    each float at its worst."""
    tab = reference.tables(config)
    trainer = config["trainer"]
    prefix = int(config["learning_check_tasks"])
    t0 = time.perf_counter()
    worst: dict = {}
    worst_ctrl: dict = {}
    out = {"checked_episodes": [], "update_steps": 0, "explore_steps": 0}
    for h in held:
        got = check_episode(tab, trainer, start_state(h.before),
                            routes[h.index % len(routes)], h.outputs,
                            prefix, h.prefix_params, reference, control)
        got["checks"]["sync_mismatch"] = int(h.synced is False)
        if control:
            got["control"]["sync_mismatch"] = 0
        out["checked_episodes"].append(h.index)
        out["update_steps"] += got["update_steps"]
        out["explore_steps"] += got["explore_steps"]
        for dst, src in ((worst, got["checks"]),
                         (worst_ctrl, got.get("control", {}))):
            for k, v in src.items():
                dst[k] = (dst.get(k, 0) + v if isinstance(v, int)
                          else max(dst.get(k, v), v))
    out["checks"] = worst
    if control:
        out["control"] = worst_ctrl
    out["reference_s"] = time.perf_counter() - t0
    return out
