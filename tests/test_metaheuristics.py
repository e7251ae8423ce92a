"""Device GA/SA parity vs the NumPy oracles: window fitness against
``ga._evaluate``, committed placements against ``HMAIPlatform.execute``,
and the vmap/shard_map layout invariances."""
import jax
import numpy as np
import pytest

from repro.core.environment import EnvironmentParams, build_task_queue
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import (spec_from_platform, state_from_platform,
                                     summarize)
from repro.core.schedulers import (GAConfig, SAConfig, get_scheduler,
                                   make_metaheuristic_fn,
                                   make_sharded_metaheuristic_fn,
                                   metaheuristic_schedule, window_fitness)
from repro.core.schedulers.ga import _evaluate
from repro.core.tasks import pad_task_arrays, stack_task_arrays, \
    tasks_to_arrays

RS = 0.05


def _queue(seed, km=0.05):
    return build_task_queue(EnvironmentParams(
        route_km=km, rate_scale=RS, seed=seed, max_times_turn=2,
        max_times_reverse=1, max_duration_turn=4.0,
        max_duration_reverse=6.0))


def _platform():
    return HMAIPlatform(capacity_scale=RS)


# ---------------------------------------------------------------------------
# window fitness vs ga._evaluate
# ---------------------------------------------------------------------------

def test_window_fitness_matches_oracle():
    """Fixed-seed fitness parity from a warm mid-route snapshot (the
    ISSUE-3 acceptance bar: <= 1e-4 relative)."""
    q = _queue(7)
    plat = _platform()
    rng = np.random.default_rng(0)
    for t in q[:60]:
        plat.execute(t, int(rng.integers(0, plat.n)))
    spec = spec_from_platform(plat)
    snap = state_from_platform(plat)
    window = q[60:90]
    wa = tasks_to_arrays(window)
    fit = jax.jit(lambda a: window_fitness(spec, snap, wa, a))
    for _ in range(16):
        assign = rng.integers(0, plat.n, len(window))
        ref = _evaluate(plat, window, assign)
        dev = float(fit(np.asarray(assign, np.int32)))
        assert dev == pytest.approx(ref, rel=1e-4)


def test_window_fitness_ignores_padding():
    q = _queue(9)
    plat = _platform()
    spec = spec_from_platform(plat)
    snap = state_from_platform(plat)
    window = q[:20]
    wa = tasks_to_arrays(window)
    wa_pad = pad_task_arrays(wa, 32)
    rng = np.random.default_rng(1)
    assign = np.asarray(rng.integers(0, plat.n, 20), np.int32)
    assign_pad = np.concatenate([assign,
                                 np.zeros(12, np.int32)])
    a = float(window_fitness(spec, snap, wa, assign))
    b = float(window_fitness(spec, snap, wa_pad, assign_pad))
    assert a == pytest.approx(b, rel=1e-6)


# ---------------------------------------------------------------------------
# committed placements vs the HMAIPlatform oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["ga", "sa"])
def test_device_commit_matches_oracle_replay(name):
    """Replaying the device search's placements through the NumPy
    platform must land on the same metrics — the commit path and the
    oracle agree on the §7.2 semantics."""
    q = _queue(11)
    summ = metaheuristic_schedule(name, _platform(), q, seed=3)
    assert summ["tasks"] == len(q)
    placements = summ["placements"]
    assert placements.shape == (len(q),)
    oracle = _platform()
    for task, a in zip(q, placements):
        oracle.execute(task, int(a))
    ref = oracle.summary()
    assert summ["makespan_s"] == pytest.approx(ref["makespan_s"], rel=1e-4)
    assert summ["total_energy_j"] == pytest.approx(ref["total_energy_j"],
                                                   rel=1e-4)
    assert summ["stm_rate"] == pytest.approx(ref["stm_rate"], abs=1e-6)
    assert summ["r_balance"] == pytest.approx(ref["r_balance"], abs=2e-3)


def test_device_ga_quality_comparable_to_numpy_ga():
    """Same fitness function, same budget: the device GA's Table-11 cost
    (makespan + 0.1 * energy) must land in the NumPy GA's ballpark."""
    q = _queue(13)
    dev = metaheuristic_schedule("ga", _platform(), q, seed=0)
    ref = get_scheduler("ga").schedule(_platform(), q)
    cost = lambda s: s["makespan_s"] + 0.1 * s["total_energy_j"]
    assert cost(dev) <= cost(ref) * 1.05


# ---------------------------------------------------------------------------
# layout invariances
# ---------------------------------------------------------------------------

def test_batched_matches_single_route():
    routes = [tasks_to_arrays(_queue(s, km=0.03)) for s in (1, 2)]
    spec = spec_from_platform(_platform())
    cfg = GAConfig(generations=4)
    single = make_metaheuristic_fn(spec, "ga", cfg)
    batched = make_metaheuristic_fn(spec, "ga", cfg, batched=True)
    batch = stack_task_arrays(routes)
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    finals_b, recs_b = batched(keys, batch)
    for lane, ta in enumerate(routes):
        final_s, recs_s = single(keys[lane], ta)
        n = ta.num_tasks
        np.testing.assert_array_equal(
            np.asarray(recs_b.action)[lane, :n][
                np.asarray(recs_b.valid)[lane, :n]],
            np.asarray(recs_s.action)[np.asarray(recs_s.valid)])
        np.testing.assert_allclose(
            np.asarray(jax.tree_util.tree_map(lambda a: a[lane],
                                              finals_b).T),
            np.asarray(final_s.T), rtol=1e-5)


def test_sharded_matches_batched():
    """shard_map over a 1-lane-per-device mesh is a pure re-layout."""
    from repro.compat import make_mesh
    n_dev = len(jax.devices())
    routes = [tasks_to_arrays(_queue(20 + s, km=0.03))
              for s in range(n_dev)]
    spec = spec_from_platform(_platform())
    cfg = SAConfig(iters=16, chains=2)
    batch = stack_task_arrays(routes)
    keys = jax.random.split(jax.random.PRNGKey(8), n_dev)
    batched = make_metaheuristic_fn(spec, "sa", cfg, batched=True)
    mesh = make_mesh((n_dev,), ("routes",))
    sharded = make_sharded_metaheuristic_fn(spec, "sa", mesh, cfg)
    f_b, r_b = jax.device_get(batched(keys, batch))
    f_s, r_s = jax.device_get(sharded(keys, batch))
    np.testing.assert_array_equal(np.asarray(r_s.action),
                                  np.asarray(r_b.action))
    for a, b in zip(f_s, f_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_state0_resume_continues_route():
    """Scheduling from a resumed state must match the oracle replay of the
    same placements over the concatenated queue."""
    q = _queue(31, km=0.03)
    cut = len(q) // 2
    spec = spec_from_platform(_platform())
    fn = make_metaheuristic_fn(spec, "ga", GAConfig(generations=3))
    key = jax.random.PRNGKey(2)
    final1, recs1 = fn(key, tasks_to_arrays(q[:cut]))
    final2, recs2 = fn(key, tasks_to_arrays(q[cut:]), final1)
    placements = np.concatenate([
        np.asarray(recs1.action)[np.asarray(recs1.valid)],
        np.asarray(recs2.action)[np.asarray(recs2.valid)]])
    oracle = _platform()
    for task, a in zip(q, placements):
        oracle.execute(task, int(a))
    summ = summarize(spec, final2, recs2)
    assert summ["makespan_s"] == pytest.approx(oracle.makespan, rel=1e-4)
    np.testing.assert_allclose(np.asarray(final2.avail), oracle.avail,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# serving edge case (satellite)
# ---------------------------------------------------------------------------

def test_placement_service_empty_input():
    from repro.core.flexai import FlexAIAgent, FlexAIConfig
    from repro.serve.qos import QoSConfig, QoSPlacementEngine
    plat = _platform()
    agent = FlexAIAgent(plat, FlexAIConfig(seed=1))
    eng = QoSPlacementEngine(plat, agent.learner.eval_p,
                             QoSConfig(policy="fifo"))
    eng.run_until_done()
    assert eng.dispatches == 0
    assert eng.stats()["completed"] == 0
