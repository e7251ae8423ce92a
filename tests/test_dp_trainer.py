"""Data-parallel fused trainer + FlexAIAgent<->ScanFlexAI weight interop.

Contracts:

* lossless weight round-trip across the two training worlds (bit-exact
  params, identical greedy placements), through objects and through the
  shared npz checkpoint format;
* the DP trainer with 1 shard / 1 lane reproduces the unsharded fused
  trainer's TrainState trajectory (identical actions and counters,
  params to fp32 tolerance);
* the shard_map'd DP trainer is a pure re-layout of the unsharded DP
  runner at equal global batch (subprocess: forced host devices must be
  set before jax imports);
* eval-based model selection on the scan path keeps the best-eval
  weights.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.environment import EnvironmentParams, build_task_queue
from repro.core.flexai import (FlexAIAgent, FlexAIConfig, ScanFlexAI,
                               dp_train_init, make_dp_train_fn,
                               make_train_fn, train_init)
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import spec_from_platform
from repro.core.tasks import (TaskArrays, stack_task_arrays,
                              tasks_to_arrays)

RS = 0.05


def _queue(seed, km=0.02):
    return build_task_queue(EnvironmentParams(
        route_km=km, rate_scale=RS, seed=seed, max_times_turn=2,
        max_times_reverse=1, max_duration_turn=4.0,
        max_duration_reverse=6.0))


def _platform():
    return HMAIPlatform(capacity_scale=RS)


def _cfg(**over):
    kw = dict(min_replay=32, batch_size=16, update_every=2,
              eps_decay_steps=500, replay_capacity=2048, seed=2)
    kw.update(over)
    return FlexAIConfig(**kw)


# ---------------------------------------------------------------------------
# weight interop
# ---------------------------------------------------------------------------

def test_agent_scan_agent_roundtrip_bit_exact():
    """FlexAIAgent -> ScanFlexAI -> FlexAIAgent preserves params
    bit-exactly and produces identical greedy placements."""
    q = _queue(33)
    agent = FlexAIAgent(_platform(), _cfg())
    trainer = ScanFlexAI.from_agent(agent, _platform())
    back = trainer.to_agent(_platform())
    for a, b in zip(agent.learner.eval_p, back.learner.eval_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s_agent = agent.schedule_scan(_platform(), q)
    s_scan = trainer.schedule(q)
    s_back = back.schedule_scan(_platform(), q)
    np.testing.assert_array_equal(s_agent["placements"],
                                  s_scan["placements"])
    np.testing.assert_array_equal(s_agent["placements"],
                                  s_back["placements"])


def test_npz_checkpoint_shared_format(tmp_path):
    """ScanFlexAI reads/writes FlexAIAgent's npz checkpoint format in
    both directions, bit-exactly — including the DP and population
    wrappers (broadcast import)."""
    path = str(tmp_path / "w.npz")
    trainer = ScanFlexAI(_platform(), _cfg())
    trainer.train_episode(_queue(31))
    trainer.save_weights(path)

    agent = FlexAIAgent(_platform(), _cfg())
    agent.load_weights(path)
    for a, b in zip(trainer.eval_params(), agent.learner.eval_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    agent_path = str(tmp_path / "a.npz")
    agent.save_weights(agent_path)
    for wrapper in (ScanFlexAI(_platform(), _cfg()),
                    ScanFlexAI(_platform(), _cfg(), lanes=2, dp=True),
                    ScanFlexAI(_platform(), _cfg(), lanes=2)):
        wrapper.load_weights(agent_path)
        for lane in range(1 if wrapper.dp else wrapper.lanes):
            for a, b in zip(wrapper.eval_params(lane),
                            trainer.eval_params()):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))


# ---------------------------------------------------------------------------
# DP trainer parity
# ---------------------------------------------------------------------------

def test_dp_one_shard_matches_unsharded_fused_trainer():
    """make_dp_train_fn with 1 lane and no mesh walks the same TrainState
    trajectory as make_train_fn: identical actions, update cadence and
    counters; params/losses to fp32 tolerance (batched-vs-vector matmul
    shapes round differently at the ulp level)."""
    q = _queue(21)
    plat = _platform()
    spec = spec_from_platform(plat)
    cfg = _cfg()
    ta = tasks_to_arrays(q)
    state_dim = 3 + 5 * plat.n
    key = jax.random.PRNGKey(cfg.seed)

    ts_s, _, recs_s, loss_s, upd_s = make_train_fn(spec, cfg)(
        train_init(key, state_dim, plat.n, cfg.replay_capacity), ta)
    ts_d, _, recs_d, loss_d, upd_d = make_dp_train_fn(spec, cfg, 1)(
        dp_train_init(key, state_dim, plat.n, cfg.replay_capacity, 1),
        TaskArrays(*[np.asarray(f)[None] for f in ta]))

    np.testing.assert_array_equal(np.asarray(recs_s.action),
                                  np.asarray(recs_d.action)[0])
    np.testing.assert_array_equal(np.asarray(upd_s, bool),
                                  np.asarray(upd_d, bool))
    assert int(ts_s.env_steps) == int(ts_d.env_steps) == len(q)
    assert int(ts_s.updates) == int(ts_d.updates)
    np.testing.assert_allclose(np.asarray(loss_s), np.asarray(loss_d),
                               atol=1e-4)
    for a, b in zip(ts_s.eval_p, ts_d.eval_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def test_dp_chunked_collectives_match_legacy_trajectory():
    """``chunk_collectives=True`` (one 2-float stats psum per step; grads
    + pmean + adam only inside the update-step cond) must walk the same
    trajectory as the legacy every-step-pmean path at equal global batch:
    identical actions and update cadence, losses/params to fp32 tolerance
    (the cond-inlined vs always-on graphs fuse differently at ulp level)."""
    plat = _platform()
    spec = spec_from_platform(plat)
    cfg = _cfg()
    batch = stack_task_arrays(
        [tasks_to_arrays(_queue(s)) for s in (21, 22)])
    sd = 3 + 5 * plat.n
    ts0 = dp_train_init(jax.random.PRNGKey(cfg.seed), sd, plat.n,
                        cfg.replay_capacity, 2)
    ts_c, _, recs_c, loss_c, upd_c = make_dp_train_fn(
        spec, cfg, 2, chunk_collectives=True)(ts0, batch)
    ts_l, _, recs_l, loss_l, upd_l = make_dp_train_fn(
        spec, cfg, 2, chunk_collectives=False)(ts0, batch)
    np.testing.assert_array_equal(np.asarray(recs_c.action),
                                  np.asarray(recs_l.action))
    np.testing.assert_array_equal(np.asarray(upd_c, bool),
                                  np.asarray(upd_l, bool))
    assert int(ts_c.env_steps) == int(ts_l.env_steps)
    assert int(ts_c.updates) == int(ts_l.updates) > 0
    np.testing.assert_allclose(np.asarray(loss_c), np.asarray(loss_l),
                               atol=1e-4)
    for a, b in zip(ts_c.eval_p, ts_l.eval_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4)


def test_dp_sharded_single_device_matches_unsharded():
    """The shard_map DP trainer on a 1-device mesh walks the unsharded
    trajectory bit-exactly, in both collective layouts.  One device
    already type-checks shard_map's varying manual axes."""
    from repro.compat import make_mesh
    plat = _platform()
    spec = spec_from_platform(plat)
    cfg = _cfg()
    batch = stack_task_arrays([tasks_to_arrays(_queue(s))
                               for s in (61, 62)])
    ts0 = dp_train_init(jax.random.PRNGKey(cfg.seed), 3 + 5 * plat.n,
                        plat.n, cfg.replay_capacity, 2)
    mesh = make_mesh((1,), ("routes",), devices=jax.devices()[:1])
    for chunk in (True, False):
        ref = make_dp_train_fn(spec, cfg, 2, chunk_collectives=chunk)(
            ts0, batch)
        out = make_dp_train_fn(spec, cfg, 2, mesh=mesh,
                               chunk_collectives=chunk)(ts0, batch)
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_dp_sharded_matches_unsharded_equal_global_batch():
    """2-device shard_map DP == unsharded DP on the same 4-route global
    batch: identical action trajectory, params to accumulated-fp32
    tolerance (pmean reduction order vs the local lane mean)."""
    script = textwrap.dedent("""
        import jax
        import numpy as np
        from repro.compat import make_mesh
        from repro.core.environment import EnvironmentParams, \\
            build_task_queue
        from repro.core.flexai import (FlexAIConfig, dp_train_init,
                                       make_dp_train_fn)
        from repro.core.hmai import HMAIPlatform
        from repro.core.platform_jax import spec_from_platform
        from repro.core.tasks import stack_task_arrays, tasks_to_arrays

        RS = 0.05
        def queue(seed):
            return build_task_queue(EnvironmentParams(
                route_km=0.02, rate_scale=RS, seed=seed, max_times_turn=2,
                max_times_reverse=1, max_duration_turn=4.0,
                max_duration_reverse=6.0))
        plat = HMAIPlatform(capacity_scale=RS)
        spec = spec_from_platform(plat)
        cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=2,
                           eps_decay_steps=500, replay_capacity=2048,
                           seed=2)
        batch = stack_task_arrays(
            [tasks_to_arrays(queue(s)) for s in (21, 22, 23, 24)])
        sd = 3 + 5 * plat.n
        ts0 = dp_train_init(jax.random.PRNGKey(cfg.seed), sd, plat.n,
                            cfg.replay_capacity, 4)
        o_u = jax.block_until_ready(
            make_dp_train_fn(spec, cfg, 4)(ts0, batch))
        mesh = make_mesh((2,), ("routes",))
        o_s = jax.block_until_ready(
            make_dp_train_fn(spec, cfg, 4, mesh=mesh)(ts0, batch))
        assert np.array_equal(np.asarray(o_u[2].action),
                              np.asarray(o_s[2].action))
        assert int(o_u[0].env_steps) == int(o_s[0].env_steps)
        assert int(o_u[0].updates) == int(o_s[0].updates)
        np.testing.assert_allclose(np.asarray(o_u[3]), np.asarray(o_s[3]),
                                   atol=1e-3)
        for a, b in zip(o_u[0].eval_p, o_s[0].eval_p):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3)
        print("OK", int(o_u[0].env_steps))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_dp_wrapper_trains_one_synchronized_agent():
    """ScanFlexAI(dp=True): one shared parameter set over the route
    batch (no per-lane weight axis), counters track the global batch,
    losses flow, greedy schedule works."""
    cfg = _cfg()
    trainer = ScanFlexAI(_platform(), cfg, lanes=2, dp=True)
    routes = [_queue(31), _queue(32)]
    out = trainer.train(routes, episodes=1)[0]
    assert len(out["lanes"]) == 2
    for lane in out["lanes"]:
        assert 0.0 <= lane["stm_rate"] <= 1.0
    # ONE agent: params have no lane axis
    assert trainer.ts.eval_p.w1.ndim == 2
    assert int(trainer.ts.env_steps) == sum(len(r) for r in routes)
    assert trainer.losses and np.isfinite(trainer.losses).all()
    s = trainer.schedule(routes[0])
    assert s["tasks"] == len(routes[0])


# ---------------------------------------------------------------------------
# eval-based model selection
# ---------------------------------------------------------------------------

def test_eval_selection_keeps_best_params():
    """train(eval_queue=...) records eval_stm on the cadence and restores
    the best-eval weights into EvalNet/TargNet at the end."""
    cfg = _cfg()
    val_q = tasks_to_arrays(_queue(50))
    trainer = ScanFlexAI(_platform(), cfg)
    hist = trainer.train([_queue(1), _queue(2)], episodes=4,
                         eval_queue=val_q, eval_every=2)
    evals = [h["eval_stm"] for h in hist if "eval_stm" in h]
    assert len(evals) == 2
    assert trainer.best_eval_stm == pytest.approx(max(evals))
    # the restored params reproduce the best recorded eval STM
    final, recs = trainer._sched_fn(trainer.eval_params(), val_q)
    from repro.core.platform_jax import summarize
    stm = summarize(trainer.spec, final, recs)["stm_rate"]
    assert stm == pytest.approx(trainer.best_eval_stm, abs=1e-9)
    # TargNet synced to the winner
    for a, b in zip(trainer.ts.eval_p, trainer.ts.targ_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_eval_selection_population_lanes():
    """Population training evaluates every lane and installs the best
    lane's weights everywhere at the end."""
    cfg = _cfg()
    trainer = ScanFlexAI(_platform(), cfg, lanes=2)
    hist = trainer.train([_queue(1), _queue(2), _queue(3), _queue(4)],
                         episodes=2, eval_queue=_queue(50), eval_every=2)
    assert isinstance(hist[1]["eval_stm"], list)
    assert len(hist[1]["eval_stm"]) == 2
    assert trainer.best_eval_stm is not None
    # broadcast import: both lanes now carry the winner
    w = np.asarray(trainer.ts.eval_p.w1)
    np.testing.assert_array_equal(w[0], w[1])
