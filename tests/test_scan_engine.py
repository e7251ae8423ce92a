"""Scan/loop parity: the device-resident engine (platform_jax + flexai
engine + scan schedulers) must reproduce the NumPy oracle path."""
import jax
import numpy as np
import pytest

from repro.core.environment import EnvironmentParams, build_task_queue
from repro.core.flexai import FlexAIAgent, FlexAIConfig, ScanFlexAI
from repro.core.flexai.engine import make_schedule_fn
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import (platform_init, platform_step,
                                     spec_from_platform, summarize)
from repro.core.schedulers import get_scheduler, scan_schedule
from repro.core.tasks import (Task, TaskKind, pad_task_arrays,
                              stack_task_arrays, tasks_to_arrays)

RS = 0.05


def _queue(seed, km=0.06):
    return build_task_queue(EnvironmentParams(
        route_km=km, rate_scale=RS, seed=seed, max_times_turn=2,
        max_times_reverse=1, max_duration_turn=4.0,
        max_duration_reverse=6.0))


def _platform():
    return HMAIPlatform(capacity_scale=RS)


# ---------------------------------------------------------------------------
# platform_step vs HMAIPlatform.execute
# ---------------------------------------------------------------------------

def test_platform_step_matches_execute():
    rng = np.random.default_rng(0)
    plat = _platform()
    spec = spec_from_platform(plat)
    state = platform_init(plat.n)
    step = jax.jit(platform_step)
    t = 0.0
    for uid in range(120):
        t += float(rng.uniform(0, 0.005))
        kind = [TaskKind.YOLO, TaskKind.SSD, TaskKind.GOTURN][uid % 3]
        task = Task(uid=uid, kind=kind, camera_group="FC", camera_id=0,
                    arrival_time=t, safety_time=0.05)
        a = int(rng.integers(0, plat.n))
        rec_np = plat.execute(task, a)
        ta = tasks_to_arrays([task])
        row = jax.tree_util.tree_map(lambda x: x[0], ta)
        state, rec = step(spec, state, row, np.int32(a))
        np.testing.assert_allclose(float(rec.response),
                                   rec_np.response_time, rtol=1e-5)
        np.testing.assert_allclose(float(rec.ms), rec_np.ms, rtol=1e-5)
        np.testing.assert_allclose(float(rec.energy), rec_np.energy,
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state.avail), plat.avail,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state.E), plat.E, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state.T), plat.T, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state.MS), plat.MS, rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state.R_Balance), plat.R_Balance,
                               rtol=1e-4)
    np.testing.assert_array_equal(np.asarray(state.num_tasks),
                                  plat.num_tasks)


# ---------------------------------------------------------------------------
# greedy inference parity (the ISSUE-1 acceptance test)
# ---------------------------------------------------------------------------

def test_schedule_scan_parity_with_loop():
    """Same weights -> same placements, STM rate and Gvalue as the Python
    loop, to fp32 tolerance."""
    q = _queue(7)
    assert len(q) > 200
    agent = FlexAIAgent(_platform(), FlexAIConfig(seed=3))

    p_loop = _platform()
    loop = agent.schedule(p_loop, q)
    loop_placements = np.asarray([r.accel_index for r in p_loop.records])

    scan = agent.schedule_scan(_platform(), q)

    np.testing.assert_array_equal(scan["placements"], loop_placements)
    assert scan["stm_rate"] == pytest.approx(loop["stm_rate"], abs=1e-6)
    assert scan["gvalue"] == pytest.approx(loop["gvalue"], rel=1e-4)
    assert scan["makespan_s"] == pytest.approx(loop["makespan_s"], rel=1e-4)
    assert scan["total_energy_j"] == pytest.approx(loop["total_energy_j"],
                                                   rel=1e-4)
    assert scan["total_ms"] == pytest.approx(loop["total_ms"], rel=1e-3)


@pytest.mark.parametrize("name", ["worst", "ata", "minmin"])
def test_heuristic_scan_parity(name):
    q = _queue(11)
    loop = get_scheduler(name).schedule(_platform(), q)
    scan = scan_schedule(name, _platform(), q)
    assert scan["tasks"] == loop["tasks"] == len(q)
    assert scan["stm_rate"] == pytest.approx(loop["stm_rate"], abs=5e-3)
    assert scan["makespan_s"] == pytest.approx(loop["makespan_s"], rel=1e-3)
    assert scan["total_energy_j"] == pytest.approx(loop["total_energy_j"],
                                                   rel=2e-3)
    assert scan["r_balance"] == pytest.approx(loop["r_balance"], abs=2e-3)


def test_state_to_platform_restores_oracle():
    """state_from_platform -> state_to_platform round-trips every §7.2
    field, and a restored oracle continues a route exactly like the
    uninterrupted one (the NumPy half of the serving preemption seam)."""
    from repro.core.platform_jax import state_from_platform, state_to_platform
    q = _queue(3, km=0.02)
    cut = len(q) // 2
    agent = FlexAIAgent(_platform(), FlexAIConfig(seed=4))
    p_full = _platform()
    agent.schedule(p_full, q)
    p_head = _platform()
    agent.schedule(p_head, q[:cut])
    p_resume = _platform()
    state_to_platform(state_from_platform(p_head), p_resume)
    np.testing.assert_allclose(p_resume.avail, p_head.avail, rtol=1e-6)
    np.testing.assert_allclose(p_resume.MS, p_head.MS, rtol=1e-6)
    np.testing.assert_array_equal(p_resume.num_tasks, p_head.num_tasks)
    agent.schedule(p_resume, q[cut:])
    np.testing.assert_allclose(p_resume.avail, p_full.avail, rtol=1e-5)
    np.testing.assert_allclose(p_resume.E, p_full.E, rtol=1e-5)
    np.testing.assert_allclose(p_resume.T, p_full.T, rtol=1e-5)
    np.testing.assert_array_equal(p_resume.num_tasks, p_full.num_tasks)


# ---------------------------------------------------------------------------
# scheduler edge cases: empty windows, single task, all-equal ties
# (the happy-path parity above never hits these branches)
# ---------------------------------------------------------------------------

def _synthetic_tasks(n, kind=TaskKind.YOLO, arrival=0.0, safety=0.05):
    return [Task(uid=i, kind=kind, camera_group="FC", camera_id=0,
                 arrival_time=arrival, safety_time=safety)
            for i in range(n)]


@pytest.mark.parametrize("name", ["worst", "ata", "minmin"])
def test_scan_single_task_parity(name):
    """A one-task route exercises the degenerate window (29 padding rows
    in Min-Min's first window; a length-1 scan elsewhere)."""
    q = _synthetic_tasks(1)
    loop = get_scheduler(name).schedule(_platform(), q)
    scan = scan_schedule(name, _platform(), q)
    assert scan["tasks"] == loop["tasks"] == 1
    assert scan["makespan_s"] == pytest.approx(loop["makespan_s"], rel=1e-5)
    assert scan["total_energy_j"] == pytest.approx(loop["total_energy_j"],
                                                   rel=1e-5)
    assert scan["stm_rate"] == loop["stm_rate"]


@pytest.mark.parametrize("name", ["worst", "ata", "minmin"])
def test_scan_empty_window_is_noop(name):
    """Padding a route to spill whole extra windows (Min-Min) / extra scan
    steps (ATA, worst) must not change any metric: all-invalid steps pass
    the platform state through."""
    from repro.core.schedulers.scan import get_scan_scheduler
    q = _queue(13, km=0.02)
    plat = _platform()
    spec = spec_from_platform(plat)
    fn = get_scan_scheduler(name)
    ta = tasks_to_arrays(q)
    # 2 fully-invalid Min-Min windows (window=30) beyond the real tasks
    padded = pad_task_arrays(ta, ta.num_tasks + 60)
    final_a, recs_a = fn(spec, ta)
    final_b, recs_b = fn(spec, padded)
    for a, b in zip(final_a, final_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert not np.asarray(recs_b.valid)[ta.num_tasks:].any()
    s_a, s_b = (summarize(spec, f, r)
                for f, r in ((final_a, recs_a), (final_b, recs_b)))
    assert s_a["tasks"] == s_b["tasks"] == len(q)
    assert s_a["stm_rate"] == pytest.approx(s_b["stm_rate"], abs=1e-9)


@pytest.mark.parametrize("name", ["ata", "minmin"])
def test_scan_all_equal_completion_time_tiebreak(name):
    """Identical tasks tie on completion time across every window row; the
    scan path's flat argmin must break ties exactly like the loop's
    strict-< first-hit (row-major), or placements drift."""
    q = _synthetic_tasks(45)  # 1.5 Min-Min windows of identical tasks
    p_loop = _platform()
    loop = get_scheduler(name).schedule(p_loop, q)
    loop_actions = np.asarray([r.accel_index for r in p_loop.records])
    scan = scan_schedule(name, _platform(), q)
    np.testing.assert_array_equal(scan["placements"], loop_actions)
    assert scan["makespan_s"] == pytest.approx(loop["makespan_s"], rel=1e-5)
    assert scan["r_balance"] == pytest.approx(loop["r_balance"], abs=1e-5)


def test_minmin_incremental_ct_matches_rebuild():
    """The default incremental completion-time carry (row->inf + one
    column recompute per commit) must be bit-identical to rebuilding the
    full [W, n] matrix every inner step — same elementwise float
    expressions, so same flat argmin and row-major tie-break."""
    from repro.core.schedulers.scan import minmin_scan
    plat = _platform()
    spec = spec_from_platform(plat)
    inc = jax.jit(lambda s, t: minmin_scan(s, t, incremental=True))
    ref = jax.jit(lambda s, t: minmin_scan(s, t, incremental=False))
    for seed in (11, 13):
        ta = tasks_to_arrays(_queue(seed, km=0.03))
        f_i, r_i = inc(spec, ta)
        f_r, r_r = ref(spec, ta)
        np.testing.assert_array_equal(np.asarray(r_i.action),
                                      np.asarray(r_r.action))
        for a, b in zip(jax.tree_util.tree_leaves((f_i, r_i)),
                        jax.tree_util.tree_leaves((f_r, r_r))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # alive-mask reroute path: masked accelerator never chosen, still exact
    import jax.numpy as jnp
    alive = jnp.ones((spec.n,), bool).at[0].set(False)
    ta = tasks_to_arrays(_queue(17, km=0.02))
    f_i, r_i = minmin_scan(spec, ta, alive=alive, incremental=True)
    f_r, r_r = minmin_scan(spec, ta, alive=alive, incremental=False)
    np.testing.assert_array_equal(np.asarray(r_i.action),
                                  np.asarray(r_r.action))
    acts = np.asarray(r_i.action)[np.asarray(r_i.valid, bool)]
    assert not (acts == 0).any()
    for a, b in zip(jax.tree_util.tree_leaves(f_i),
                    jax.tree_util.tree_leaves(f_r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# vmapped multi-route batching
# ---------------------------------------------------------------------------

def test_vmap_batch_matches_single_route():
    routes = [tasks_to_arrays(_queue(s)) for s in (1, 2)]
    plat = _platform()
    agent = FlexAIAgent(plat, FlexAIConfig(seed=5))
    spec = spec_from_platform(plat)
    params = agent.learner.eval_p

    single = make_schedule_fn(spec, agent.cfg.backlog_scale)
    batched = make_schedule_fn(spec, agent.cfg.backlog_scale, batched=True)
    batch = stack_task_arrays(routes)
    finals_b, recs_b = batched(params, batch)

    for lane, ta in enumerate(routes):
        final_s, recs_s = single(params, ta)
        n = ta.num_tasks
        np.testing.assert_array_equal(
            np.asarray(recs_b.action)[lane, :n],
            np.asarray(recs_s.action))
        np.testing.assert_allclose(
            np.asarray(jax.tree_util.tree_map(lambda a: a[lane],
                                              finals_b).T),
            np.asarray(final_s.T), rtol=1e-5)


def test_padding_is_noop():
    """Invalid rows must leave the platform state untouched."""
    ta = tasks_to_arrays(_queue(4))
    plat = _platform()
    agent = FlexAIAgent(plat, FlexAIConfig(seed=1))
    spec = spec_from_platform(plat)
    fn = make_schedule_fn(spec, agent.cfg.backlog_scale)
    final_a, recs_a = fn(agent.learner.eval_p, ta)
    padded = pad_task_arrays(ta, ta.num_tasks + 37)
    final_b, recs_b = fn(agent.learner.eval_p, padded)
    for a, b in zip(final_a, final_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert not np.asarray(recs_b.valid)[ta.num_tasks:].any()
    s_a = summarize(spec, final_a, recs_a)
    s_b = summarize(spec, final_b, recs_b)
    assert s_a["tasks"] == s_b["tasks"]
    assert s_a["stm_rate"] == pytest.approx(s_b["stm_rate"], abs=1e-9)


# ---------------------------------------------------------------------------
# fused training episode
# ---------------------------------------------------------------------------

def test_train_episode_scan_smoke():
    q = _queue(21, km=0.03)
    cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=2,
                       eps_decay_steps=500, replay_capacity=4096, seed=2)
    trainer = ScanFlexAI(_platform(), cfg)
    summ = trainer.train_episode(q)
    assert summ["tasks"] == len(q)
    assert 0.0 <= summ["stm_rate"] <= 1.0
    assert int(trainer.ts.env_steps) == len(q)
    assert int(trainer.ts.replay.size) == min(len(q), 4096)
    assert trainer.losses and np.isfinite(trainer.losses).all()
    assert summ["mean_loss"] is not None
    # counters persist across episodes (epsilon keeps decaying)
    trainer.train_episode(q)
    assert int(trainer.ts.env_steps) == 2 * len(q)


def test_schedule_scan_cache_not_shared_across_platforms():
    """Two platforms with equal n but different hardware tables must not
    reuse one compiled closure (regression: cache keyed only on n)."""
    q = _queue(17, km=0.03)
    agent = FlexAIAgent(_platform(), FlexAIConfig(seed=9))
    p_fast = HMAIPlatform(capacity_scale=RS)
    p_slow = HMAIPlatform(capacity_scale=RS / 4)
    assert p_fast.n == p_slow.n
    agent.schedule_scan(p_fast, q)  # populate the cache
    scan = agent.schedule_scan(p_slow, q)
    p_ref = HMAIPlatform(capacity_scale=RS / 4)
    loop = agent.schedule(p_ref, q)
    assert scan["makespan_s"] == pytest.approx(loop["makespan_s"], rel=1e-4)
    np.testing.assert_array_equal(
        scan["placements"], [r.accel_index for r in p_ref.records])


def test_train_episode_padded_route_matches_unpadded():
    """Padding rows must not shift the terminal transition: the replay
    ring holds exactly one done=1 row per episode either way."""
    q = _queue(23, km=0.02)
    cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=4,
                       eps_decay_steps=500, replay_capacity=4096, seed=8)
    plain = ScanFlexAI(_platform(), cfg)
    plain.train_episode(tasks_to_arrays(q))
    padded = ScanFlexAI(_platform(), cfg)
    padded.train_episode(pad_task_arrays(tasks_to_arrays(q), len(q) + 50))
    assert int(plain.ts.env_steps) == int(padded.ts.env_steps) == len(q)
    assert int(plain.ts.replay.size) == int(padded.ts.replay.size)
    for tr in (plain, padded):
        done = np.asarray(tr.ts.replay.done)[: int(tr.ts.replay.size)]
        assert done.sum() == pytest.approx(1.0)


def test_train_vmapped_lanes_smoke():
    routes = [_queue(31, km=0.03), _queue(32, km=0.03)]
    cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=4,
                       eps_decay_steps=500, replay_capacity=2048, seed=4)
    trainer = ScanFlexAI(_platform(), cfg, lanes=2)
    out = trainer.train(routes, episodes=1)[0]  # round-robins lanes
    assert len(out["lanes"]) == 2
    for lane in out["lanes"]:
        assert 0.0 <= lane["stm_rate"] <= 1.0
    # lanes are independent seeds: EvalNet weights must differ
    w0 = np.asarray(trainer.ts.eval_p.w1)[0]
    w1 = np.asarray(trainer.ts.eval_p.w1)[1]
    assert not np.allclose(w0, w1)
    # greedy schedule from a trained lane works
    s = trainer.schedule(routes[0], lane=1)
    assert s["tasks"] == len(routes[0])


# ---------------------------------------------------------------------------
# serving path
# ---------------------------------------------------------------------------

def _serve_fifo(plat, params, backlog_scale, queues, slots=4):
    """Serve ``queues`` through FIFO QoS waves; the engine and each
    queue's request."""
    from repro.serve.qos import QoSConfig, QoSPlacementEngine
    eng = QoSPlacementEngine(
        plat, params, QoSConfig(policy="fifo", slots=slots, min_bucket=64),
        backlog_scale=backlog_scale)
    reqs = [eng.submit(q) for q in queues]
    eng.run_until_done()
    return eng, reqs


def test_placement_service_buckets_and_trims():
    plat = _platform()
    agent = FlexAIAgent(plat, FlexAIConfig(seed=6))
    queues = [_queue(41, km=0.02), _queue(42, km=0.03), _queue(43, km=0.02)]
    eng, reqs = _serve_fifo(plat, agent.learner.eval_p,
                            agent.cfg.backlog_scale, queues)
    assert len(reqs) == len(queues)
    for q, r in zip(queues, reqs):
        assert r.summary["tasks"] == len(q)
        assert r.summary["placements"].shape == (len(q),)
        assert r.summary["bucket"] >= len(q)
    # same-bucket queues share a wave
    assert eng.stats()["waves"] == len({r.summary["bucket"] for r in reqs})


def test_wave_lanes_place_as_if_served_alone():
    """Each lane's scan is independent: three queues co-batched in one
    four-lane wave get the placements and STM they get one per wave."""
    plat = _platform()
    agent = FlexAIAgent(plat, FlexAIConfig(seed=6))
    queues = [_queue(41, km=0.02), _queue(42, km=0.02), _queue(43, km=0.02)]
    runs = {slots: _serve_fifo(plat, agent.learner.eval_p,
                               agent.cfg.backlog_scale, queues, slots=slots)
            for slots in (1, 4)}
    assert runs[1][0].stats()["waves"] == len(queues)
    assert runs[4][0].stats()["waves"] < len(queues)
    for alone, batched in zip(runs[1][1], runs[4][1]):
        np.testing.assert_array_equal(alone.summary["placements"],
                                      batched.summary["placements"])
        assert alone.summary["stm_rate"] == pytest.approx(
            batched.summary["stm_rate"], abs=1e-9)


# ---------------------------------------------------------------------------
# cached exec-time table (satellite)
# ---------------------------------------------------------------------------

def test_exec_time_table_matches_specs():
    plat = _platform()
    from repro.core.tasks import KIND_ORDER
    for i, spec in enumerate(plat.specs):
        for j, kind in enumerate(KIND_ORDER):
            assert plat.exec_time_table[i, j] == pytest.approx(
                spec.exec_time(kind))
            assert plat.energy_table[i, j] == pytest.approx(
                spec.energy(kind))
