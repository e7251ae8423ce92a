"""Plain JAX reference of one FlexAI DQN training episode (arXiv 2104.10415,
§7.1 and §8.3) on the HMAI platform.

Imports nothing of the program.  Given a configuration file's tables, the
trainer's state before an episode (as plain arrays), the episode's tasks
and the actions the program took, it trains the episode again with the
program's actions (teacher forcing) and returns, per step and at the end:

* the exploration draw and the random action drawn (the trainer's PRNG
  contract: one ``split(key, 4)`` per step into the next key and the
  keys of the epsilon draw, the random action and the replay sample);
* the reference Q values at the step's observation under the reference's
  own parameters, so the gap of a greedy action can be read;
* the float32 deadline verdict of the action, by the clock arithmetic of
  ``hmai_placement.replay`` (``start = max(arrival, avail[a])``, ``finish
  = start + exec``, ``met = finish - arrival <= safety``);
* the loss of each TD update, and the EvalNet parameters at the end.

One step: epsilon from the step count; the observation (Task-Info, then
per accelerator energy share, log backlog, balance, mean Matching Score
and the kind's execution time — ``hmai_placement.replay``'s observation,
here in float32 as the trainer computes it); the platform update of
§7.2; the reward dGvalue + dMS; a write into the replay ring; and every
``update_every`` steps once the ring holds ``min_replay`` transitions a
TD update: a uniform batch from the ring, the double-DQN target, the
Huber loss, gradients by ``jax.grad``, a global-norm clip at 10, Adam
with bias correction, and the TargNet copied every ``target_sync_every``
updates.

Departures from the paper, each as the program has it: the loss is
Huber and not squared; the target is double DQN (the paper's [12]);
gradients are clipped and applied with Adam at lr 1e-3 (the paper: 0.01);
the observation carries each accelerator's execution time of the task's
kind beside the HW-Info of §7.2.

Everything is float32 under ``jax.default_matmul_precision("highest")``.
The control (``lowp``) is the same episode one step below: the Q-net's
input and activations in bfloat16, its matmuls (acting and TD) at
"high" (three bfloat16 passes), the gradient, the Adam moments and the
new parameters rounded to bfloat16 at every update, and a bfloat16 clock
for its verdicts.  Its greedy actions are its own argmax.

The episode runs as one ``lax.scan``, the update under ``lax.cond``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference.hmai_placement import GOTURN, tables as placement_tables

HIGHEST = jax.lax.Precision.HIGHEST
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
GRAD_CLIP = 10.0


def tables(config: dict) -> dict:
    """Float32 tables of a configuration: execution time and energy
    ``[n, kinds]``, the Task-Info features, and the Gvalue scales (the
    mean task time and energy over the platform, §6.2)."""
    tab = placement_tables(dict(config, engine={
        "backlog_scale": config["trainer"]["backlog_scale"]}))
    return {"exec": np.asarray(tab["exec"], np.float32),
            "energy": np.asarray(tab["energy"], np.float32),
            "feat": np.asarray(tab["feat"], np.float32),
            "n": tab["n"], "backlog_scale": tab["backlog_scale"],
            "t_scale": np.float32(tab["exec"].mean()),
            "e_scale": np.float32(tab["energy"].mean())}


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _matmul(a, b, lowp: bool):
    if not lowp:
        return jnp.matmul(a, b, precision=HIGHEST)
    # "high": hi*hi + hi*lo + lo*hi in bfloat16 pieces, float32 sums
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    return (mm(a_hi, b_hi) + mm(a_hi, b_lo)) + mm(a_lo, b_hi)


def qnet(params, x, lowp: bool = False):
    w1, b1, w2, b2, w3, b3 = params
    r = _bf16 if lowp else (lambda v: v)
    h = r(jax.nn.relu(_matmul(r(x), w1, lowp) + b1))
    h = r(jax.nn.relu(_matmul(h, w2, lowp) + b2))
    return r(_matmul(h, w3, lowp) + b3)


def td_loss(params, targ, batch, gamma: float, lowp: bool = False):
    """Mean Huber loss (delta 1) of the double-DQN target: EvalNet picks
    the next action, TargNet values it."""
    rows = jnp.arange(batch["a"].shape[0])
    q_sel = qnet(params, batch["s"], lowp)[rows, batch["a"]]
    a_star = jnp.argmax(qnet(params, batch["s_next"], lowp), axis=-1)
    q_tn = qnet(targ, batch["s_next"], lowp)[rows, a_star]
    y = jax.lax.stop_gradient(
        batch["r"] + gamma * (1.0 - batch["done"]) * q_tn)
    err = y - q_sel
    return jnp.mean(jnp.where(jnp.abs(err) <= 1.0, 0.5 * err * err,
                              jnp.abs(err) - 0.5))


def _observe(tab, backlog_scale, plat, kind, arrival, safety):
    n_tasks = jnp.maximum(plat["cnt"], 1.0)
    hw = jnp.stack([
        plat["E"] / jnp.maximum(plat["e_scale"], 1e-9),
        jnp.log1p(jnp.maximum(plat["avail"] - arrival, 0.0)
                  / backlog_scale),
        plat["RB"], plat["MS"] / n_tasks, tab["exec"][:, kind]], axis=1)
    return jnp.concatenate([tab["feat"][kind], safety[None],
                            hw.reshape(-1)])


def _gvalue(tab, plat):
    e = plat["E"].sum() / jnp.maximum(
        tab["e_scale"] * jnp.maximum(plat["cnt"].sum(), 1.0), 1e-12)
    t = plat["T"].max() / jnp.maximum(tab["t_scale"], 1e-12)
    return (-e - t + plat["RB"].mean()) / 3.0


def _step_platform(tab, plat, kind, arrival, safety, a, valid):
    """§7.2's update of accelerator ``a`` for one task (float32)."""
    et, en = tab["exec"][a, kind], tab["energy"][a, kind]
    start = jnp.maximum(arrival, plat["avail"][a])
    finish = start + et
    response = finish - arrival
    met = response <= safety
    ms = jnp.where(kind == GOTURN, jnp.where(met, 1.0, -1.0),
                   jnp.where(met & (safety > 0),
                             response / jnp.maximum(safety, 1e-12), -1.0))
    new = dict(plat)
    new["avail"] = plat["avail"].at[a].set(finish)
    new["busy"] = plat["busy"].at[a].add(et)
    new["E"] = plat["E"].at[a].add(en)
    new["T"] = plat["T"].at[a].max(finish)
    new["MS"] = plat["MS"].at[a].add(ms)
    new["cnt"] = plat["cnt"].at[a].add(1.0)
    util = new["busy"][a] / jnp.maximum(finish, 1e-9)
    c = new["cnt"][a]
    new["RB"] = plat["RB"].at[a].set((util + plat["RB"][a] * (c - 1.0)) / c)
    new["e_scale"] = jnp.maximum(plat["e_scale"], new["E"].sum())
    new = {k: jnp.where(valid, new[k], plat[k]) for k in plat}
    return new, met


def _clock16(avail16, arrival, safety, e32, a):
    """The control's bfloat16 clock for one task (``hmai_placement``)."""
    s16 = jnp.maximum(_bf16(arrival), avail16[a])
    f16 = _bf16(s16 + _bf16(e32))
    r16 = _bf16(f16 - _bf16(arrival))
    return f16, r16 <= _bf16(safety)


def _adam(p, g, m, v, step, lr, lowp):
    r = _bf16 if lowp else (lambda x: x)
    c1 = 1.0 - ADAM_B1 ** step
    c2 = 1.0 - ADAM_B2 ** step
    m = r(ADAM_B1 * m + (1.0 - ADAM_B1) * g)
    v = r(ADAM_B2 * v + (1.0 - ADAM_B2) * g * g)
    return r(p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS)), m, v


@functools.lru_cache(maxsize=4)
def _episode_fn(trainer: tuple, n_actions: int, backlog_scale: float,
                lowp: bool):
    """The jitted episode for a trainer block (as sorted items)."""
    tr = dict(trainer)
    gamma, lr, B = tr["gamma"], tr["lr"], tr["batch_size"]
    r = _bf16 if lowp else (lambda x: x)

    def update(c, k_smp):
        ring = c["ring"]
        idx = jax.random.randint(k_smp, (B,), 0,
                                 jnp.maximum(ring["size"], 1))
        batch = {f: ring[f][idx] for f in ("s", "a", "r", "s_next", "done")}
        loss, grads = jax.value_and_grad(td_loss)(
            c["eval_p"], c["targ_p"], batch, gamma, lowp)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads))
        clip = jnp.minimum(1.0, GRAD_CLIP / jnp.maximum(gnorm, 1e-9))
        step = c["opt_step"] + 1
        out = [_adam(p, r(g * clip), m, v, step.astype(jnp.float32), lr,
                     lowp)
               for p, g, m, v in zip(c["eval_p"], grads, c["mu"], c["nu"])]
        eval_p = tuple(o[0] for o in out)
        updates = c["updates"] + 1
        sync = updates % tr["target_sync_every"] == 0
        targ_p = tuple(jnp.where(sync, e, t)
                       for e, t in zip(eval_p, c["targ_p"]))
        return dict(c, eval_p=eval_p, targ_p=targ_p, opt_step=step,
                    mu=tuple(o[1] for o in out), nu=tuple(o[2] for o in out),
                    updates=updates), loss

    def body(tab, c, x):
        kind, arrival, safety, valid, nkind, narrival, nsafety, done, a = x
        key, k_eps, k_act, k_smp = jax.random.split(c["key"], 4)
        frac = jnp.minimum(1.0, c["env_steps"].astype(jnp.float32)
                           / max(tr["eps_decay_steps"], 1))
        eps = tr["eps_start"] + (tr["eps_end"] - tr["eps_start"]) * frac
        explore = jax.random.uniform(k_eps) < eps
        r_act = jax.random.randint(k_act, (), 0, n_actions)
        q = qnet(c["eval_p"], c["sv"], lowp)

        plat2, met = _step_platform(tab, c["plat"], kind, arrival, safety,
                                    a, valid)
        reward = (_gvalue(tab, plat2) - _gvalue(tab, c["plat"])
                  + (plat2["MS"].sum() - c["plat"]["MS"].sum()))
        nsv = _observe(tab, backlog_scale, plat2, nkind, narrival, nsafety)
        avail16, met16 = _clock16(c["avail16"], arrival, safety,
                                  tab["exec"][a, kind], a)
        avail16 = jnp.where(valid, c["avail16"].at[a].set(avail16),
                            c["avail16"])

        ring = dict(c["ring"])
        p = ring["ptr"]
        for f, val in (("s", c["sv"]), ("a", a), ("r", reward),
                       ("s_next", nsv), ("done", done.astype(jnp.float32))):
            ring[f] = ring[f].at[p].set(jnp.where(valid, val, ring[f][p]))
        cap = ring["s"].shape[0]
        ring["ptr"] = jnp.where(valid, (p + 1) % cap, p)
        ring["size"] = jnp.where(valid, jnp.minimum(ring["size"] + 1, cap),
                                 ring["size"])
        env_steps = c["env_steps"] + valid.astype(jnp.int32)
        do_update = (valid & (ring["size"] >= tr["min_replay"])
                     & (env_steps % tr["update_every"] == 0))
        c = dict(c, key=key, plat=plat2, sv=nsv, ring=ring,
                 env_steps=env_steps, avail16=avail16)
        c, loss = jax.lax.cond(do_update, update,
                               lambda c, _: (c, jnp.float32(0.0)), c, k_smp)
        return c, {"explore": explore, "random_action": r_act, "q": q,
                   "met": met, "met16": met16, "loss": loss,
                   "update": do_update}

    def run(tab, start, xs):
        with jax.default_matmul_precision("highest"):
            c, ys = jax.lax.scan(functools.partial(body, tab), start, xs)
        return ys, c["eval_p"]

    return jax.jit(run)


def replay(tab: dict, trainer: dict, start: dict, tasks: dict, actions,
           lowp: bool = False) -> dict:
    """Train one episode of ``tasks`` again from ``start`` with the
    program's ``actions``.

    ``trainer``: the configuration's trainer block.  ``start``: ``eval_p``,
    ``targ_p``, ``mu``, ``nu`` (six arrays each), ``opt_step``, ``ring``
    (``s``, ``a``, ``r``, ``s_next``, ``done`` of the replay capacity,
    ``ptr``, ``size``), ``env_steps``, ``updates`` and ``key`` (the raw
    ``uint32[2]`` PRNG key).  ``tasks``: ``kind``, ``arrival``,
    ``safety``, ``valid`` of the episode.  Returns per-step arrays
    (``explore``, ``random_action``, ``q`` [T, n], ``met``, ``met16``,
    ``loss``, ``update``) and ``eval_p``, the parameters at the end."""
    kind = np.asarray(tasks["kind"], np.int32)
    arrival = np.asarray(tasks["arrival"], np.float32)
    safety = np.asarray(tasks["safety"], np.float32)
    valid = np.asarray(tasks["valid"], bool)
    T = kind.shape[0]
    # each transition pairs with the next valid task; the last valid one
    # pairs with itself and ends the episode
    nxt = np.arange(T) + 1
    nxt = np.where((nxt < T) & valid[np.minimum(nxt, T - 1)], nxt,
                   np.arange(T))
    done = np.arange(T) == valid.sum() - 1
    n = tab["n"]
    z = jnp.zeros((n,), jnp.float32)
    plat = {"avail": z, "busy": z, "E": z, "T": z, "MS": z, "RB": z,
            "cnt": z, "e_scale": jnp.float32(1e-9)}
    tabj = {k: jnp.asarray(v) for k, v in tab.items()
            if k in ("exec", "energy", "feat", "t_scale", "e_scale")}
    sv0 = _observe(tabj, tab["backlog_scale"], plat, jnp.int32(kind[0]),
                   jnp.float32(arrival[0]), jnp.float32(safety[0]))
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    ring = start["ring"]
    c0 = {
        "eval_p": tuple(map(f32, start["eval_p"])),
        "targ_p": tuple(map(f32, start["targ_p"])),
        "mu": tuple(map(f32, start["mu"])),
        "nu": tuple(map(f32, start["nu"])),
        "opt_step": jnp.int32(start["opt_step"]),
        "ring": {"s": f32(ring["s"]), "a": jnp.asarray(ring["a"], jnp.int32),
                 "r": f32(ring["r"]), "s_next": f32(ring["s_next"]),
                 "done": f32(ring["done"]),
                 "ptr": jnp.int32(ring["ptr"]),
                 "size": jnp.int32(ring["size"])},
        "env_steps": jnp.int32(start["env_steps"]),
        "updates": jnp.int32(start["updates"]),
        "key": jnp.asarray(start["key"], jnp.uint32),
        "plat": plat, "sv": sv0, "avail16": z,
    }
    xs = (kind, arrival, safety, valid, kind[nxt], arrival[nxt],
          safety[nxt], done, np.asarray(actions, np.int32))
    tr_key = tuple(sorted((k, v) for k, v in trainer.items()
                          if isinstance(v, (int, float))
                          and not isinstance(v, bool)))
    fn = _episode_fn(tr_key, n, float(tab["backlog_scale"]), bool(lowp))
    ys, eval_p = fn(tabj, c0, xs)
    out = jax.device_get(ys)
    out["eval_p"] = [np.asarray(p) for p in jax.device_get(eval_p)]
    return out
