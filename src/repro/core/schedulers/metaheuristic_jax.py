"""Device-resident GA/SA metaheuristics on the pure platform substrate.

The NumPy baselines (``ga.py`` / ``sa.py``) re-simulate the platform one
task per Python iteration, per individual, per generation — O(pop x
generations x window) ``_evaluate`` platform simulations for every window
of every route.  Here the whole windowed search runs inside one
``lax.scan`` over windows:

* ``window_fitness``     — the Table-11 guided-random-search fitness
  (-(makespan + 0.1 * energy)) scanned over a window's ``TaskArrays``
  slice from a *snapshot* ``PlatformState`` (``state_from_platform``),
  mutating nothing.
* ``ga`` window search   — a ``lax.fori_loop`` over generations with the
  fitness ``vmap``-ed over the population axis: elite selection by sorted
  fitness, uniform parent draws among elites, one-point crossover and
  masked mutation, all driven by ``jax.random``.
* ``sa`` window search   — ``chains`` independent annealing chains
  (vmapped): single-task reassignment proposals on a geometric
  temperature ladder with Metropolis acceptance; best state over all
  chains wins.
* route driver           — an outer ``lax.scan`` walks the route window
  by window, committing the winning assignment through ``platform_step``
  (the same transition the FlexAI scan engine uses), so a route
  schedules in one device dispatch and the search is ``vmap``-able over
  a leading route axis and shard_map-able over the ``("routes",)`` mesh
  seam (``make_sharded_metaheuristic_fn`` + ``tasks.pad_route_batch``).

The NumPy ``GAScheduler``/``SAScheduler`` stay registered as the parity
oracles; ``tests/test_metaheuristics.py`` pins the fitness arithmetic and
the committed-placement semantics to them.  See DESIGN.md ("Vectorized
metaheuristic substrate").
"""
from __future__ import annotations

import time
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.compat import vary_like
from repro.core.faults import window_health
from repro.core.platform_jax import (PlatformSpec, PlatformState,
                                     health_capacity, platform_init,
                                     platform_step, spec_from_platform,
                                     with_health)
from repro.core.schedulers.base import Scheduler, register
from repro.core.tasks import TaskArrays, tasks_to_arrays, window_task_arrays


class GAConfig(NamedTuple):
    """Mirrors ``GAScheduler``'s hyperparameters (paper Table 11)."""
    window: int = 30
    population: int = 16
    generations: int = 10
    mutation: float = 0.1


class SAConfig(NamedTuple):
    """Mirrors ``SAScheduler``; ``chains`` parallel annealing chains are
    the population axis the device path adds (chains=1 == the oracle's
    single trajectory, modulo the RNG stream).

    ``tempering=True`` switches the chains from independent Kirkpatrick
    annealing (every chain walks the same decaying temperature schedule)
    to **parallel tempering**: each chain holds a FIXED temperature on a
    geometric ladder from ``t_start`` (hot, chain 0) to ``t_end`` (cold),
    and every ``exchange_every`` iterations adjacent chains attempt a
    replica-exchange Metropolis swap.  Fidelity note: this is no longer
    Kirkpatrick SA — there is no cooling schedule, so per-chain behaviour
    does not converge on the oracle's trajectory; what it buys is mixing
    (hot chains tunnel out of local minima and hand good states down the
    ladder), which at equal iteration budgets gives equal-or-better best
    fitness with the chains the device path already vmaps for free.
    """
    window: int = 30
    iters: int = 120
    t_start: float = 1.0
    t_end: float = 0.01
    chains: int = 8
    tempering: bool = False
    exchange_every: int = 10


# ---------------------------------------------------------------------------
# window fitness (the pure mirror of ga._evaluate)
# ---------------------------------------------------------------------------

def _maxplus_reduce(c: jax.Array, d: jax.Array):
    """Order-preserving reduction of the affine max-plus maps
    ``g_k(x) = max(x + c_k, d_k)`` along axis 0.

    The maps are closed under composition — ``(g2 . g1)`` has
    ``c = c1 + c2`` and ``d = max(d1 + c2, d2)`` — with identity
    ``(0, -inf)``, so the window folds in ``log2(W)`` pairwise combines of
    fully-vectorized arrays instead of a W-step sequential scan.
    """
    w = c.shape[0]
    pad = (1 << max(w - 1, 1).bit_length()) - w
    c = jnp.concatenate([c, jnp.zeros((pad,) + c.shape[1:], c.dtype)])
    d = jnp.concatenate([d, jnp.full((pad,) + d.shape[1:], -jnp.inf,
                                     d.dtype)])
    while c.shape[0] > 1:
        c0, c1 = c[0::2], c[1::2]
        d0, d1 = d[0::2], d[1::2]
        c = c0 + c1
        d = jnp.maximum(d0 + c1, d1)
    return c[0], d[0]


def window_fitness(spec: PlatformSpec, state: PlatformState,
                   wtasks: TaskArrays, assignment: jax.Array) -> jax.Array:
    """Fitness = -(makespan + 0.1 * energy) of ``assignment`` simulated on
    a scratch copy of ``state`` — arithmetic-identical to ``ga._evaluate``
    on the NumPy platform (time + energy only, no R_Balance/MS terms).

    Each accelerator's FIFO queueing recurrence
    ``f_k = max(arrival_k, f_{k-1}) + et_k`` (tasks not assigned to it
    pass ``f`` through) is an affine max-plus map, so the window evaluates
    in ``log2(W)`` vectorized combines (``_maxplus_reduce``) rather than a
    sequential scan — this is what lets one generation score the whole
    population as a single [P, W, n] tensor op.  Invalid (padding) rows
    are identity maps and contribute no energy.
    """
    a = assignment.astype(jnp.int32)
    # health scale from the snapshot state: throttled cores inflate
    # et/energy by 1/capacity, dead cores by 1/HEALTH_FLOOR — fitness
    # pressure alone drives genes off dead cores, no explicit masking
    # (all-healthy divides by exactly 1.0: the oracle parity is intact)
    eff = health_capacity(state)
    et = spec.exec_time[a, wtasks.kind] / eff[a]              # [W]
    onehot = ((a[:, None] == jnp.arange(spec.n)[None, :])
              & wtasks.valid[:, None])                        # [W, n]
    energy = jnp.sum(jnp.where(wtasks.valid,
                               spec.energy[a, wtasks.kind] / eff[a], 0.0))
    c = jnp.where(onehot, et[:, None], 0.0)
    d = jnp.where(onehot, (wtasks.arrival + et)[:, None], -jnp.inf)
    c_all, d_all = _maxplus_reduce(c, d)
    finish = jnp.maximum(state.avail + c_all, d_all)          # [n]
    # idle accelerators fold in as avail_i, which never exceeds T.max()
    makespan = jnp.maximum(jnp.max(state.T), jnp.max(finish))
    return -(makespan + 0.1 * energy)


# ---------------------------------------------------------------------------
# window searches
# ---------------------------------------------------------------------------

def _ga_window(spec: PlatformSpec, cfg: GAConfig, state: PlatformState,
               wtasks: TaskArrays, key: jax.Array) -> jax.Array:
    """One GA window search; returns the best assignment vector [W]."""
    w = wtasks.arrival.shape[0]
    pop, n_elite = cfg.population, cfg.population // 2
    n_child = pop - n_elite
    fitness = jax.vmap(lambda a: window_fitness(spec, state, wtasks, a))
    k_init, k_loop = jax.random.split(key)
    population = jax.random.randint(k_init, (pop, w), 0, spec.n, jnp.int32)

    def gen(_, carry):
        population, key = carry
        key, k_par, k_cx, k_mut, k_val = jax.random.split(key, 5)
        order = jnp.argsort(-fitness(population))
        elite = population[order[:n_elite]]
        parents = elite[jax.random.randint(k_par, (n_child, 2), 0, n_elite)]
        cx = jax.random.randint(k_cx, (n_child, 1), 1, max(w, 2))
        child = jnp.where(jnp.arange(w)[None, :] < cx,
                          parents[:, 0], parents[:, 1])
        mut = jax.random.uniform(k_mut, (n_child, w)) < cfg.mutation
        child = jnp.where(
            mut, jax.random.randint(k_val, (n_child, w), 0, spec.n,
                                    jnp.int32), child)
        return jnp.concatenate([elite, child]), key

    population, _ = jax.lax.fori_loop(0, cfg.generations, gen,
                                      (population, k_loop), unroll=2)
    return population[jnp.argmax(fitness(population))]


def _sa_window(spec: PlatformSpec, cfg: SAConfig, state: PlatformState,
               wtasks: TaskArrays, key: jax.Array) -> jax.Array:
    """SA over ``cfg.chains`` vmapped annealing chains; best chain wins.

    With ``cfg.tempering`` the chains become parallel-tempering replicas:
    fixed per-chain temperatures on the geometric ladder plus periodic
    adjacent-chain exchange moves (see :class:`SAConfig`).  The default
    keeps the decaying-schedule Kirkpatrick chains bit-exactly (the
    tempering branch is compiled out and the PRNG stream is untouched)."""
    w = wtasks.arrival.shape[0]
    c = cfg.chains
    fitness = jax.vmap(lambda a: window_fitness(spec, state, wtasks, a))
    k_init, k_loop = jax.random.split(key)
    cur = jax.random.randint(k_init, (c, w), 0, spec.n, jnp.int32)
    cur_fit = fitness(cur)
    if cfg.tempering:
        # chain 0 hottest -> chain c-1 coldest, fixed for the whole window
        ladder = cfg.t_start * (cfg.t_end / cfg.t_start) ** (
            jnp.arange(c, dtype=jnp.float32) / max(c - 1, 1))

    def it(i, carry):
        cur, cur_fit, best, best_fit, key = carry
        if cfg.tempering:
            temp = ladder                                     # [c]
        else:
            frac = i.astype(jnp.float32) / max(cfg.iters - 1, 1)
            temp = cfg.t_start * (cfg.t_end / cfg.t_start) ** frac
        key, k_pos, k_val, k_acc = jax.random.split(key, 4)
        pos = jax.random.randint(k_pos, (c,), 0, w)
        val = jax.random.randint(k_val, (c,), 0, spec.n, jnp.int32)
        cand = cur.at[jnp.arange(c), pos].set(val)
        fit = fitness(cand)
        # exponent clipped at 0: uphill moves are accepted unconditionally
        # by the first clause, and exp() must not overflow for them
        p_acc = jnp.exp(jnp.minimum(
            (fit - cur_fit) / jnp.maximum(temp, 1e-9), 0.0))
        accept = (fit > cur_fit) | (jax.random.uniform(k_acc, (c,)) < p_acc)
        cur = jnp.where(accept[:, None], cand, cur)
        cur_fit = jnp.where(accept, fit, cur_fit)
        if cfg.tempering:
            # replica exchange: alternating even/odd adjacent pairs, the
            # standard exp((beta_j - beta_k)(E_j - E_k)) swap acceptance
            # with E = -fitness; one shared coin per pair (the left
            # member's draw) so both sides take the same decision
            key, k_ex = jax.random.split(key)
            idx = jnp.arange(c)
            parity = ((i + 1) // max(cfg.exchange_every, 1)) % 2
            left = (idx % 2 == parity) & (idx < c - 1)
            partner = jnp.where(left, idx + 1,
                                jnp.where(jnp.roll(left, 1), idx - 1, idx))
            beta = 1.0 / jnp.maximum(ladder, 1e-9)
            delta = (beta - beta[partner]) * (cur_fit[partner] - cur_fit)
            u = jax.random.uniform(k_ex, (c,))
            u_pair = jnp.where(left, u, u[partner])
            due = (i + 1) % max(cfg.exchange_every, 1) == 0
            swap = ((u_pair < jnp.exp(jnp.minimum(delta, 0.0)))
                    & (partner != idx) & due)
            cur = jnp.where(swap[:, None], cur[partner], cur)
            cur_fit = jnp.where(swap, cur_fit[partner], cur_fit)
        improved = cur_fit > best_fit
        best = jnp.where(improved[:, None], cur, best)
        best_fit = jnp.maximum(best_fit, cur_fit)
        return cur, cur_fit, best, best_fit, key

    # the ladder is 120 tiny dependent steps; partial unroll keeps the
    # loop-iteration overhead from dominating the vectorized proposals
    _, _, best, best_fit, _ = jax.lax.fori_loop(
        0, cfg.iters, it, (cur, cur_fit, cur, cur_fit, k_loop),
        unroll=8)
    return best[jnp.argmax(best_fit)]


_WINDOW_SEARCHES = {"ga": (_ga_window, GAConfig),
                    "sa": (_sa_window, SAConfig)}


# ---------------------------------------------------------------------------
# route driver: scan over windows, commit through platform_step
# ---------------------------------------------------------------------------

def _route_run(spec: PlatformSpec, cfg, search):
    """Un-jitted single-route runner: ``run(key, tasks, state0=None) ->
    (final_state, records)`` — the shared core the jitted, vmapped and
    shard_mapped entry points wrap (same layering as the FlexAI engine)."""
    window = cfg.window

    def commit(state, x):
        task, a = x
        return platform_step(spec, state, task, a)

    def win_body(carry, x):
        wtasks, hrow = x
        state, key = carry
        # windowed granularity contract (core.faults): the health row at
        # the window's first task index holds for the whole window, so
        # the search's fitness and the committed platform_steps agree
        state = with_health(state, hrow)
        key, k_w = jax.random.split(key)
        best = search(spec, cfg, state, wtasks, k_w)
        # partial unroll only: the commit body is scatter-heavy and a
        # full unroll sends XLA compile time past 10 minutes
        state2, recs = jax.lax.scan(commit, state, (wtasks, best),
                                    unroll=6)
        return (state2, key), recs

    def run(key, tasks: TaskArrays, state0: PlatformState | None = None,
            health=None):
        win = window_task_arrays(tasks, window)
        trace = (jnp.ones((tasks.arrival.shape[0], spec.n), jnp.float32)
                 if health is None else jnp.asarray(health, jnp.float32))
        init = platform_init(spec.n) if state0 is None else state0
        init, trace = vary_like((init, trace), tasks.arrival)
        (state, _), recs = jax.lax.scan(win_body, (init, key),
                                        (win, window_health(trace, window)))
        recs = jax.tree_util.tree_map(
            lambda a: a.reshape(-1, *a.shape[2:]), recs)
        return state, recs

    return run


def make_metaheuristic_fn(spec: PlatformSpec, name: str, cfg=None,
                          batched: bool = False):
    """Compile the windowed device search ``name`` ("ga" / "sa").

    Returns ``fn(key, tasks[, state0]) -> (final_state, records)``; with
    ``batched=True`` both ``key`` [R, ...] and ``tasks`` [R, T] carry a
    leading route axis (no ``state0`` on the batched path).
    """
    search, cfg_cls = _WINDOW_SEARCHES[name]
    cfg = cfg_cls() if cfg is None else cfg
    run = _route_run(spec, cfg, search)
    if batched:
        single = run

        def run(key, tasks, health=None):
            if health is None:
                return jax.vmap(single, in_axes=(0, 0))(key, tasks)
            return jax.vmap(lambda k, t, h: single(k, t, health=h),
                            in_axes=(0, 0, 0))(key, tasks, health)
    return jax.jit(run)


def make_sharded_metaheuristic_fn(spec: PlatformSpec, name: str, mesh,
                                  cfg=None, axis: str = "routes"):
    """Multi-device variant: the vmapped route batch splits over
    ``mesh``'s ``axis`` with shard_map (keys and tasks both shard on the
    route axis; R must be a mesh-size multiple — ``pad_route_batch``).
    Window searches are route-local, so no collectives are involved."""
    from jax.sharding import PartitionSpec as P

    search, cfg_cls = _WINDOW_SEARCHES[name]
    cfg = cfg_cls() if cfg is None else cfg
    run = jax.vmap(_route_run(spec, cfg, search), in_axes=(0, 0))
    sharded = jax.shard_map(run, mesh=mesh, in_specs=(P(axis), P(axis)),
                            out_specs=P(axis))
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# host-side scheduler wrappers (registry names "ga_scan" / "sa_scan")
# ---------------------------------------------------------------------------

class _DeviceMetaheuristic(Scheduler):
    """``Scheduler.schedule`` surface over the device search: same summary
    keys, one device dispatch per route.  The NumPy platform argument
    supplies the hardware tables only and is left untouched (the committed
    state lives in the returned summary, like ``scan_schedule``)."""
    search_name = ""

    def __init__(self, cfg=None, seed: int = 0):
        self.cfg = cfg
        self.seed = seed
        self._cache: dict = {}

    def _fn(self, platform, spec):
        key = (platform.exec_time_table.tobytes(),
               platform.energy_table.tobytes())
        if key not in self._cache:
            self._cache[key] = make_metaheuristic_fn(
                spec, self.search_name, self.cfg)
        return self._cache[key]

    def schedule(self, platform, tasks) -> dict:
        from repro.core.schedulers.scan import package_device_summary
        spec = spec_from_platform(platform)
        ta = tasks if isinstance(tasks, TaskArrays) else \
            tasks_to_arrays(tasks)
        fn = self._fn(platform, spec)
        t0 = time.perf_counter()
        final, recs = fn(jax.random.PRNGKey(self.seed), ta)
        jax.block_until_ready(final)
        dt = time.perf_counter() - t0
        return package_device_summary(spec, final, recs, dt, ta.num_tasks)


@register
class DeviceGAScheduler(_DeviceMetaheuristic):
    name = "ga_scan"
    search_name = "ga"


@register
class DeviceSAScheduler(_DeviceMetaheuristic):
    name = "sa_scan"
    search_name = "sa"


def metaheuristic_schedule(name: str, platform, tasks, cfg=None,
                           seed: int = 0) -> dict:
    """Convenience mirror of ``scan_schedule`` for the GA/SA families."""
    cls = {"ga": DeviceGAScheduler, "sa": DeviceSAScheduler}[name]
    return cls(cfg=cfg, seed=seed).schedule(platform, tasks)
