"""Pipeline parallelism over the heterogeneous mesh (ISSUE 7).

Contracts:

* the flattened wavefront engine is bit-exact against the unpipelined
  task-major reference (EFT and stage-FlexAI policies);
* a 1-stage pipeline with the task-level policy IS the existing scan
  engine (bit-exact state and records);
* the stage-share decomposition is honest: per-stage exec times sum back
  to the whole-model exec table (no accelerator gets faster in aggregate);
* route batches padded to a lane multiple (``pad_route_batch``) change
  nothing for the real lanes;
* a wavefront segment split at any chunk cut resumes bit-exactly from the
  ``(state, ring)`` checkpoint — the QoS preemption contract;
* QoS pipeline waves (``cfg.stages > 1``) serve real stage placements:
  a solo request reproduces the direct pipeline schedule, and preemption/
  resume does not change any placement;
* stage-level FlexAI trains end-to-end on the scan path and, on a
  single-stage workload, is no worse than the task-level agent;
* (slow) the shard_map'd engine on a (2, 2) ``("stages", "routes")`` mesh
  reproduces the flattened engine bit-exactly, ring hops via ppermute.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.environment import EnvironmentParams, build_task_queue
from repro.core.flexai import FlexAIAgent, FlexAIConfig, ScanFlexAI
from repro.core.flexai.engine import make_schedule_fn
from repro.core.hmai import HMAIPlatform
from repro.core.pipeline import (PipelineFlexAI, build_stage_plan,
                                 make_pipeline_reference_fn,
                                 make_pipeline_schedule_fn,
                                 _pipeline_segment_run, _wavefront_stream)
from repro.core.platform_jax import spec_from_platform
from repro.core.tasks import (pad_route_batch, pad_task_arrays,
                              stack_task_arrays, tasks_to_arrays)

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


def _trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


# ---------------------------------------------------------------------------
# share-model honesty
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stages", [2, 3])
def test_stage_exec_decomposes_exec_table(stages):
    """Per-stage exec (and energy) must sum back to the whole-model
    tables: splitting a model into stages redistributes work, it never
    makes an accelerator faster in aggregate."""
    plat = _platform()
    spec = spec_from_platform(plat)
    plan = build_stage_plan(plat, stages)
    np.testing.assert_allclose(
        np.asarray(plan.stage_exec).sum(0), np.asarray(spec.exec_time),
        rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(plan.stage_energy).sum(0), np.asarray(spec.energy),
        rtol=1e-5)
    # every accelerator belongs to exactly one group; every stage has one
    groups = np.asarray(plan.groups)
    assert set(groups.tolist()) == set(range(stages))
    mask = np.asarray(plan.group_mask)
    np.testing.assert_array_equal(mask, np.arange(stages)[:, None] == groups)


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["eft", "flexai"])
def test_flattened_matches_reference(policy):
    plat = _platform()
    spec = spec_from_platform(plat)
    plan = build_stage_plan(plat, 2)
    params = (None if policy == "eft"
              else PipelineFlexAI(plat, _cfg(), n_stages=2).eval_params())
    ta = tasks_to_arrays(_queue(31))
    flat = make_pipeline_schedule_fn(spec, plan, policy=policy)
    ref = make_pipeline_reference_fn(spec, plan, policy=policy)
    assert _trees_equal(flat(params, ta), ref(params, ta))


def test_one_stage_task_policy_is_the_scan_engine():
    """S=1 pipeline with the task-level policy == make_schedule_fn:
    identical final state, and the [T, 1] stage records squeeze to the
    scan engine's [T] records."""
    plat = _platform()
    spec = spec_from_platform(plat)
    plan = build_stage_plan(plat, 1)
    params = FlexAIAgent(plat, _cfg()).learner.eval_p
    ta = tasks_to_arrays(_queue(32))
    f_p, _, r_p = make_pipeline_schedule_fn(spec, plan,
                                            policy="task")(params, ta)
    f_s, r_s = make_schedule_fn(spec)(params, ta)
    assert _trees_equal(f_p, f_s)
    assert _trees_equal(
        jax.tree_util.tree_map(lambda a: a[:, 0], r_p), r_s)


def test_padded_route_batch_is_inert():
    """pad_route_batch to a lane multiple: real lanes unchanged, padding
    lanes record nothing."""
    plat = _platform()
    spec = spec_from_platform(plat)
    plan = build_stage_plan(plat, 2)
    routes = [tasks_to_arrays(_queue(s)) for s in (33, 34, 35)]
    batch = pad_route_batch(stack_task_arrays(routes), 4)
    assert batch.arrival.shape[0] == 4
    fn = make_pipeline_schedule_fn(spec, plan, policy="eft", batched=True)
    fB, _, rB = fn(None, batch)
    T = batch.arrival.shape[1]
    solo = make_pipeline_schedule_fn(spec, plan, policy="eft")
    for lane, r in enumerate(routes):
        fL, _, rL = solo(None, pad_task_arrays(r, T))
        assert _trees_equal(
            jax.tree_util.tree_map(lambda a, l=lane: a[l], (fB, rB)),
            (fL, rL))
    assert not np.asarray(rB.valid)[3].any()


def test_segment_resume_bit_exact():
    """Splitting the flat wavefront at a segment cut and resuming from the
    (state, ring) checkpoint reproduces the single-pass run bit-exactly —
    the QoS preemption/resume contract."""
    plat = _platform()
    spec = spec_from_platform(plat)
    plan = build_stage_plan(plat, 2)
    params = PipelineFlexAI(plat, _cfg(), n_stages=2).eval_params()
    ta = tasks_to_arrays(_queue(36))
    rows, s_seq = _wavefront_stream(ta, 2)
    run = jax.jit(_pipeline_segment_run(spec, plan))
    f1, ring1, r1 = run(params, rows, s_seq)
    cut = 2 * (rows.arrival.shape[0] // 5)
    sl = lambda t, a, b: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x[a:b], t)
    fa, ra, rec_a = run(params, sl(rows, 0, cut), s_seq[:cut])
    fb, rb, rec_b = run(params, sl(rows, cut, None), s_seq[cut:], fa, ra)
    assert _trees_equal(f1, fb)
    assert _trees_equal(ring1, rb)
    joined = jax.tree_util.tree_map(
        lambda a, b: np.concatenate([np.asarray(a), np.asarray(b)]),
        rec_a, rec_b)
    assert _trees_equal(r1, joined)


# ---------------------------------------------------------------------------
# QoS pipeline waves
# ---------------------------------------------------------------------------

def _stage_agent(plat):
    return PipelineFlexAI(plat, _cfg(), n_stages=2)


def test_qos_pipeline_wave_matches_direct_schedule():
    """A solo request through stages=2 QoS serving reproduces the direct
    flattened pipeline schedule of the same (bucket-padded) route."""
    from repro.serve.qos import QoSConfig, QoSPlacementEngine
    plat = _platform()
    pipe = _stage_agent(plat)
    q = _queue(37)
    cfg = QoSConfig(policy="edf", stages=2, slots=2, min_bucket=16)
    eng = QoSPlacementEngine(plat, pipe.eval_params(), cfg,
                             backlog_scale=pipe.cfg.backlog_scale)
    req = eng.submit(q)
    eng.run_until_done()
    assert req.status == "completed"
    assert req.summary["stages"] == 2
    assert req.summary["placements"].shape == (len(q), 2)
    direct = pipe.schedule(pad_task_arrays(tasks_to_arrays(q), req.bucket))
    np.testing.assert_array_equal(req.summary["placements"],
                                  direct["placements"][: len(q)])
    assert req.summary["stm_rate"] == pytest.approx(direct["stm_rate"],
                                                    abs=1e-9)


def test_qos_pipeline_preemption_does_not_change_placements():
    """Pipeline waves preempt at flat segment cuts with a (state, ring)
    checkpoint; placements must be identical with preemption on or off."""
    from repro.serve.qos import QoSConfig, QoSPlacementEngine
    plat = _platform()
    pipe = _stage_agent(plat)
    routes = [_queue(38, km=0.03), _queue(39), _queue(40)]

    def serve(preempt):
        cfg = QoSConfig(policy="edf", stages=2, slots=1, min_bucket=16,
                        preempt=preempt, laxity_s=1e-4, shed=False)
        eng = QoSPlacementEngine(plat, pipe.eval_params(), cfg,
                                 backlog_scale=pipe.cfg.backlog_scale)
        # the long route starts first with a slack deadline; tighter
        # routes arrive mid-wave and must preempt it at a segment cut
        eng.submit(routes[0], arrival=0.0, deadline=1e6)
        eng.submit(routes[1], arrival=1e-4, deadline=0.05)
        eng.submit(routes[2], arrival=2e-4, deadline=0.06)
        eng.run_until_done()
        return eng

    on, off = serve(True), serve(False)
    assert on.preemption_count > 0
    by_uid = {r.uid: r for r in off.completed}
    assert len(on.completed) == len(routes)
    for r in on.completed:
        np.testing.assert_array_equal(r.summary["placements"],
                                      by_uid[r.uid].summary["placements"])


def test_durability_rejects_pipeline_waves():
    from repro.serve.durability import DurableQoSEngine
    from repro.serve.qos import QoSConfig
    plat = _platform()
    pipe = _stage_agent(plat)
    with pytest.raises(ValueError, match="pipeline"):
        DurableQoSEngine(plat, pipe.eval_params(),
                         QoSConfig(stages=2))


# ---------------------------------------------------------------------------
# stage-level FlexAI training
# ---------------------------------------------------------------------------

def test_stage_flexai_trains_and_matches_task_agent_on_one_stage():
    """The stage agent must learn end-to-end on the scan path (updates
    fire, losses recorded), and with a single stage — where placement is
    the same problem the task agent solves — its scheduled STM must be no
    worse (small tolerance; the two nets see different state encodings)."""
    plat = _platform()
    queues = [_queue(41), _queue(42)]
    eval_q = _queue(43)
    cfg = _cfg(update_every=1, eps_decay_steps=300)

    pipe1 = PipelineFlexAI(plat, cfg, n_stages=1)
    pipe1.train(queues, episodes=30, eval_queue=eval_q, eval_every=3)
    assert len(pipe1.losses) > 0
    stage_stm = pipe1.schedule(eval_q)["stm_rate"]

    task = ScanFlexAI(plat, cfg)
    task.train(queues, episodes=30, eval_queue=eval_q, eval_every=3)
    task_stm = task.schedule(eval_q)["stm_rate"]
    assert stage_stm >= task_stm - 0.05

    # and the 2-stage agent trains on the same pool
    pipe2 = PipelineFlexAI(plat, cfg, n_stages=2)
    hist = pipe2.train(queues, episodes=4)
    assert len(pipe2.losses) > 0
    assert all(h["stages"] == 2 for h in hist)


# ---------------------------------------------------------------------------
# sharded engine (subprocess: forced host devices before jax imports)
# ---------------------------------------------------------------------------

def test_sharded_pipeline_single_device_parity():
    """The pipeline's three shard_map paths on a 1-device mesh — the
    stage-sharded wavefront, the population trainer and the DP trainer —
    reproduce their unsharded twins bit-exactly.  One device already
    type-checks shard_map's varying manual axes."""
    from repro.compat import make_mesh
    from repro.core.flexai.engine import dp_train_init, train_init
    from repro.core.pipeline import (combine_stage_states,
                                     make_pipeline_dp_train_fn,
                                     make_pipeline_train_fn,
                                     make_sharded_pipeline_fn,
                                     make_sharded_pipeline_train_fn,
                                     stage_state_dim)
    plat = _platform()
    spec = spec_from_platform(plat)
    plan = build_stage_plan(plat, 1)
    batch = stack_task_arrays([tasks_to_arrays(_queue(s))
                               for s in (46, 47)])
    one = jax.devices()[:1]

    f_fl, _, r_fl = make_pipeline_schedule_fn(
        spec, plan, policy="eft", batched=True)(None, batch)
    mesh2 = make_mesh((1, 1), ("stages", "routes"), devices=one)
    st, _, rc = make_sharded_pipeline_fn(spec, plan, mesh2,
                                         policy="eft")(None, batch)
    assert all(np.array_equal(np.asarray(a).transpose(1, 2, 0),
                              np.asarray(b))
               for a, b in zip(jax.tree_util.tree_leaves(rc),
                               jax.tree_util.tree_leaves(r_fl)))
    assert _trees_equal(combine_stage_states(plan, st), f_fl)

    cfg = _cfg()
    sd = stage_state_dim(plat.n)
    mesh = make_mesh((1,), ("routes",), devices=one)
    ts = jax.vmap(lambda k: train_init(k, sd, plat.n, cfg.replay_capacity))(
        jax.random.split(jax.random.PRNGKey(cfg.seed), 2))
    assert _trees_equal(
        make_sharded_pipeline_train_fn(spec, plan, cfg, mesh)(ts, batch),
        make_pipeline_train_fn(spec, plan, cfg, batched=True)(ts, batch))
    ts = dp_train_init(jax.random.PRNGKey(cfg.seed), sd, plat.n,
                       cfg.replay_capacity, 2)
    assert _trees_equal(
        make_pipeline_dp_train_fn(spec, plan, cfg, 2, mesh=mesh)(ts, batch),
        make_pipeline_dp_train_fn(spec, plan, cfg, 2)(ts, batch))


@pytest.mark.slow
def test_sharded_pipeline_matches_flattened():
    script = textwrap.dedent("""
        import jax
        import numpy as np
        from repro.core.environment import EnvironmentParams, \\
            build_task_queue
        from repro.core.hmai import HMAIPlatform
        from repro.core.pipeline import (build_stage_plan,
                                         combine_stage_states,
                                         make_pipeline_schedule_fn,
                                         make_sharded_pipeline_fn)
        from repro.core.platform_jax import spec_from_platform
        from repro.core.tasks import stack_task_arrays, tasks_to_arrays
        from repro.launch.mesh import make_platform_mesh

        RS = 0.05
        def queue(seed):
            return build_task_queue(EnvironmentParams(
                route_km=0.02, rate_scale=RS, seed=seed, max_times_turn=2,
                max_times_reverse=1, max_duration_turn=4.0,
                max_duration_reverse=6.0))
        plat = HMAIPlatform(capacity_scale=RS)
        spec = spec_from_platform(plat)
        plan = build_stage_plan(plat, 2)
        batch = stack_task_arrays(
            [tasks_to_arrays(queue(s)) for s in (44, 45)])
        mesh = make_platform_mesh(2)
        assert dict(zip(mesh.axis_names, mesh.devices.shape)) == \\
            {"stages": 2, "routes": 2}
        f_fl, _, r_fl = make_pipeline_schedule_fn(
            spec, plan, policy="eft", batched=True)(None, batch)
        st, _, rc = make_sharded_pipeline_fn(
            spec, plan, mesh, policy="eft")(None, batch)
        for a, b in zip(jax.tree_util.tree_leaves(rc),
                        jax.tree_util.tree_leaves(r_fl)):
            assert np.array_equal(np.asarray(a).transpose(1, 2, 0),
                                  np.asarray(b))
        comb = combine_stage_states(plan, st)
        for a, b in zip(jax.tree_util.tree_leaves(comb),
                        jax.tree_util.tree_leaves(f_fl)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        print("OK", int(np.asarray(batch.valid).sum()))
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
