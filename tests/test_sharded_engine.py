"""Multi-device FlexAI engine: the shard_map'd schedule/train paths must be
pure re-layouts of the vmapped single-device engine.  Multi-device cases run
in subprocesses (``--xla_force_host_platform_device_count`` must be set
before jax imports); route-batch padding is covered in-process."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.tasks import (invalid_task_arrays, pad_route_batch,
                              stack_task_arrays, tasks_to_arrays)


def _run_sub(script: str, devices: int, timeout=420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


_PRELUDE = textwrap.dedent("""
    import jax
    import numpy as np
    from repro.compat import make_mesh
    from repro.core.environment import EnvironmentParams, build_task_queue
    from repro.core.flexai import (FlexAIAgent, FlexAIConfig, ScanFlexAI,
                                   make_schedule_fn,
                                   make_sharded_schedule_fn)
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import (pad_route_batch, stack_task_arrays,
                                  tasks_to_arrays)
    RS = 0.05
    def queue(seed, km=0.02):
        return build_task_queue(EnvironmentParams(
            route_km=km, rate_scale=RS, seed=seed, max_times_turn=2,
            max_times_reverse=1, max_duration_turn=4.0,
            max_duration_reverse=6.0))
    plat = HMAIPlatform(capacity_scale=RS)
    spec = spec_from_platform(plat)
""")


@pytest.mark.slow
def test_sharded_schedule_matches_vmapped():
    """4-device shard_map schedule == plain vmapped scan: identical
    placements, final platform states to fp32 tolerance.  6 routes on 4
    devices exercises the pad_route_batch path."""
    script = _PRELUDE + textwrap.dedent("""
        agent = FlexAIAgent(plat, FlexAIConfig(seed=3))
        routes = [tasks_to_arrays(queue(s)) for s in range(6)]
        batch = pad_route_batch(stack_task_arrays(routes), 4)
        mesh = make_mesh((4,), ("routes",))
        f_sh, r_sh = jax.device_get(
            make_sharded_schedule_fn(spec, mesh)(
                agent.learner.eval_p, batch))
        f_pl, r_pl = jax.device_get(
            make_schedule_fn(spec, batched=True)(
                agent.learner.eval_p, batch))
        assert np.array_equal(np.asarray(r_sh.action),
                              np.asarray(r_pl.action))
        for a, b in zip(f_sh, f_pl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
        # padding lanes stayed no-ops
        assert not np.asarray(r_sh.valid)[len(routes):].any()
        print("OK", batch.arrival.shape[0])
    """)
    out = _run_sub(script, devices=4)
    assert "OK 8" in out


@pytest.mark.slow
def test_sharded_train_runs_and_lanes_differ():
    """ScanFlexAI over a 2-device mesh: one fused episode per lane, lanes
    keep independent seeds/weights, counters advance like the local path."""
    script = _PRELUDE + textwrap.dedent("""
        cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=4,
                           eps_decay_steps=500, replay_capacity=2048,
                           seed=4)
        mesh = make_mesh((2,), ("routes",))
        tr = ScanFlexAI(plat, cfg, lanes=2, mesh=mesh)
        routes = [queue(31), queue(32)]
        out = tr.train(routes, episodes=1)[0]
        assert len(out["lanes"]) == 2
        for lane in out["lanes"]:
            assert 0.0 <= lane["stm_rate"] <= 1.0
        w = np.asarray(tr.ts.eval_p.w1)
        assert not np.allclose(w[0], w[1])
        steps = np.asarray(tr.ts.env_steps)
        assert steps[0] == len(routes[0]) and steps[1] == len(routes[1])
        s = tr.schedule(routes[0], lane=0)
        assert s["tasks"] == len(routes[0])
        print("OK")
    """)
    out = _run_sub(script, devices=2)
    assert "OK" in out


def test_sharded_engine_single_device_parity():
    """shard_map over a 1-device ("routes",) mesh is a pure re-layout of
    the vmapped greedy schedule and of the population trainer.  One
    device already type-checks shard_map's varying manual axes, so this
    pins the multi-device paths without forced host devices."""
    import jax

    from repro.compat import make_mesh
    from repro.core.environment import EnvironmentParams, build_task_queue
    from repro.core.flexai import FlexAIAgent, FlexAIConfig
    from repro.core.flexai.engine import (make_schedule_fn,
                                          make_sharded_schedule_fn,
                                          make_sharded_train_fn,
                                          make_train_fn, train_init)
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import spec_from_platform

    rs = 0.05
    plat = HMAIPlatform(capacity_scale=rs)
    spec = spec_from_platform(plat)
    batch = pad_route_batch(stack_task_arrays([tasks_to_arrays(
        build_task_queue(EnvironmentParams(
            route_km=0.02, rate_scale=rs, seed=s, max_times_turn=2,
            max_times_reverse=1, max_duration_turn=4.0,
            max_duration_reverse=6.0))) for s in (51, 52)]), 1)
    mesh = make_mesh((1,), ("routes",), devices=jax.devices()[:1])

    def assert_same(a, b):
        for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(a)),
                        jax.tree_util.tree_leaves(jax.device_get(b))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    params = FlexAIAgent(plat, FlexAIConfig(seed=3)).learner.eval_p
    assert_same(make_sharded_schedule_fn(spec, mesh)(params, batch),
                make_schedule_fn(spec, batched=True)(params, batch))

    cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=4,
                       eps_decay_steps=500, replay_capacity=1024, seed=4)
    ts = jax.vmap(lambda k: train_init(k, 3 + 5 * plat.n, plat.n,
                                       cfg.replay_capacity))(
        jax.random.split(jax.random.PRNGKey(cfg.seed), 2))
    assert_same(make_sharded_train_fn(spec, cfg, mesh)(ts, batch),
                make_train_fn(spec, cfg, batched=True)(ts, batch))


def test_benchmark_arm_runs_in_process(monkeypatch):
    """The path a chip takes for a multi-device benchmark arm: the same
    ``--child`` body in this process over the first devices, its tagged
    result parsed from captured stdout."""
    import jax

    from benchmarks.common import run_device_arm
    from benchmarks.sharded_engine import RESULT_TAG

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    res = run_device_arm(
        "sharded_engine", 1,
        ["--lanes", 2, "--tasks", 32, "--iters", 1, "--unique-routes", 2],
        RESULT_TAG)
    assert res["devices"] == 1 and res["lanes"] == 2
    assert res["placements_equal"] and res["metric_rel_diff_max"] < 1e-4


def test_forced_device_child_refused_off_cpu(monkeypatch):
    """Off the CPU backend the parent holds the chip, so a child that
    needs it would fail or hang: spawning one is refused up front."""
    import jax

    from benchmarks.common import spawn_forced_device_child

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU backend only"):
        spawn_forced_device_child("sharded_engine", 2, [], "UNUSED ")


def test_pad_route_batch_shapes_and_validity():
    routes = [invalid_task_arrays(10) for _ in range(3)]
    for i, r in enumerate(routes):
        r.valid[: 4 + i] = True
    batch = stack_task_arrays(routes)
    padded = pad_route_batch(batch, 4)
    assert padded.arrival.shape == (4, 10)
    assert not padded.valid[3].any()          # padding lane all-invalid
    np.testing.assert_array_equal(padded.valid[:3], batch.valid)
    # already a multiple: unchanged object
    assert pad_route_batch(padded, 2) is padded


def test_invalid_route_is_noop_through_engine():
    """A fully-invalid lane must leave its platform state at init."""
    import jax
    from repro.core.flexai import FlexAIAgent, FlexAIConfig, \
        make_schedule_fn
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import spec_from_platform
    plat = HMAIPlatform(capacity_scale=0.05)
    spec = spec_from_platform(plat)
    agent = FlexAIAgent(plat, FlexAIConfig(seed=0))
    fn = make_schedule_fn(spec)
    final, recs = fn(agent.learner.eval_p, invalid_task_arrays(32))
    assert not np.asarray(recs.valid).any()
    np.testing.assert_array_equal(np.asarray(final.num_tasks),
                                  np.zeros(plat.n, np.int32))
    np.testing.assert_array_equal(np.asarray(final.E),
                                  np.zeros(plat.n, np.float32))
