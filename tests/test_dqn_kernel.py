"""Fused TD-update kernel: parity against the autodiff oracle.

The oracle is the production trainer math itself
(``repro.core.flexai.dqn``): ``dqn_td_grads`` = ``jax.value_and_grad``
over the Huber double-DQN loss + 10.0 global-norm clip, ``dqn_td_update``
= grads + ``adam_apply``.  The kernel re-derives the backward by hand and
fuses everything into one Pallas pass, so every test here is a parity
pin, not a behavior spec.

Execution mode follows ``repro.kernels.protocol``: interpret on the CPU
backend, compiled on any other.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flexai.dqn import (DQNParams, _adam_init, adam_apply,
                                   dqn_td_grads, dqn_td_update, init_qnet)
from repro.kernels.dqn_update import (dqn_td_grads_fused,
                                      dqn_td_update_fused)
from repro.kernels.protocol import compiled_available

KEY = jax.random.PRNGKey(11)
INTERPRET = not compiled_available()
D, A = 18, 3  # state_dim / n_actions of the 3-core HMAI platform


def _nets(key):
    ep = init_qnet(key, D, A)
    tp = init_qnet(jax.random.fold_in(key, 99), D, A)
    return ep, tp


def _batch(key, b, done_rate=0.2):
    ks = jax.random.split(key, 5)
    return {
        "s": jax.random.normal(ks[0], (b, D), jnp.float32),
        "a": jax.random.randint(ks[1], (b,), 0, A),
        "r": jax.random.normal(ks[2], (b,), jnp.float32) * 3.0,
        "s_next": jax.random.normal(ks[3], (b, D), jnp.float32),
        "done": (jax.random.uniform(ks[4], (b,))
                 < done_rate).astype(jnp.float32),
    }


def _assert_grads_close(g_ref: DQNParams, g_ker: DQNParams, tol=1e-5):
    for name, a, b in zip(g_ref._fields, g_ref, g_ker):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("b,tile", [
    (8, 128),    # single tile, tile > B
    (32, 128),   # the engine default shape
    (64, 16),    # multi-tile, exact division
    (40, 16),    # B NOT a multiple of the tile -> masked tail block
    (17, 8),     # prime B, masked tail
])
def test_grads_parity_vs_value_and_grad(b, tile):
    ep, tp = _nets(KEY)
    batch = _batch(jax.random.fold_in(KEY, b), b)
    loss_ref, g_ref = dqn_td_grads(ep, tp, batch)
    loss_ker, g_ker = dqn_td_grads_fused(ep, tp, batch, batch_tile=tile,
                                         interpret=INTERPRET)
    np.testing.assert_allclose(float(loss_ker), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(g_ref, g_ker)


def test_grads_parity_all_done_batch():
    """done = 1 everywhere: the bootstrap term vanishes (y = r), so the
    TargNet forward must contribute exactly nothing."""
    ep, tp = _nets(KEY)
    batch = _batch(jax.random.fold_in(KEY, 1), 32)
    batch["done"] = jnp.ones_like(batch["done"])
    loss_ref, g_ref = dqn_td_grads(ep, tp, batch)
    loss_ker, g_ker = dqn_td_grads_fused(ep, tp, batch,
                                         interpret=INTERPRET)
    np.testing.assert_allclose(float(loss_ker), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(g_ref, g_ker)


def test_grads_parity_no_done_and_gamma():
    ep, tp = _nets(jax.random.fold_in(KEY, 5))
    batch = _batch(jax.random.fold_in(KEY, 2), 24)
    batch["done"] = jnp.zeros_like(batch["done"])
    loss_ref, g_ref = dqn_td_grads(ep, tp, batch, gamma=0.5)
    loss_ker, g_ker = dqn_td_grads_fused(ep, tp, batch, gamma=0.5,
                                         interpret=INTERPRET)
    np.testing.assert_allclose(float(loss_ker), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(g_ref, g_ker)


@pytest.mark.parametrize("nudge", [1.0 - 1e-3, 1.0, 1.0 + 1e-3])
def test_clip_boundary_gnorm_exactly_ten(nudge):
    """Engineered batch whose UNclipped gradient norm is exactly 10.0
    (the clip threshold), then nudged just below / onto / just above it.

    Construction: s = 0 and b1 = 0 kill layer 1 (h1 = 0); b2 = c makes
    h2 = c on all 64 lanes; w3 = 0, b3 = 0 make every Q zero; a huge
    reward saturates the Huber (per-sample dL/dq_sel = -1/B) and every
    sample takes action 0, so the only nonzero gradients are
    dW3[:, 0] = -c (64 entries) and db3[0] = -1:
    gnorm = sqrt(64 c^2 + 1) = 10  <=>  c = sqrt(99/64).
    The kernel's clip factor must track the oracle through the boundary.
    """
    b = 16
    c = float(np.sqrt(99.0 / 64.0)) * nudge
    h1, h2 = 256, 64
    zeros = DQNParams(
        w1=jnp.zeros((D, h1)), b1=jnp.zeros((h1,)),
        w2=jnp.zeros((h1, h2)), b2=jnp.full((h2,), c),
        w3=jnp.zeros((h2, A)), b3=jnp.zeros((A,)))
    batch = {
        "s": jnp.zeros((b, D)), "a": jnp.zeros((b,), jnp.int32),
        "r": jnp.full((b,), 100.0), "s_next": jnp.zeros((b, D)),
        "done": jnp.ones((b,), jnp.float32),
    }
    loss_ref, g_ref = dqn_td_grads(zeros, zeros, batch)
    loss_ker, g_ker = dqn_td_grads_fused(zeros, zeros, batch,
                                         interpret=INTERPRET)
    gnorm_ref = float(jnp.sqrt(sum(jnp.sum(g * g) for g in g_ref)))
    gnorm_ker = float(jnp.sqrt(sum(jnp.sum(g * g) for g in g_ker)))
    # post-clip norms agree to 1e-5 AND sit where the construction says:
    # min(10, gnorm_unclipped) with gnorm_unclipped = 10 * nudge-ish
    np.testing.assert_allclose(gnorm_ker, gnorm_ref, rtol=1e-5, atol=1e-6)
    assert gnorm_ref <= 10.0 + 1e-4
    np.testing.assert_allclose(float(loss_ker), float(loss_ref),
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(g_ref, g_ker)


def test_update_parity_vs_dqn_td_update():
    ep, tp = _nets(KEY)
    opt = _adam_init(ep)
    batch = _batch(jax.random.fold_in(KEY, 3), 64)
    p_ref, o_ref, l_ref = dqn_td_update(ep, tp, opt, batch)
    p_ker, o_ker, l_ker = dqn_td_update_fused(ep, tp, opt, batch,
                                              interpret=INTERPRET)
    np.testing.assert_allclose(float(l_ker), float(l_ref),
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(p_ref, p_ker)
    _assert_grads_close(o_ref.mu, o_ker.mu)
    _assert_grads_close(o_ref.nu, o_ker.nu)
    assert int(o_ker.step) == int(o_ref.step) == 1


def test_update_trajectory_64_updates_within_1e5():
    """The acceptance pin: >= 64 consecutive fused updates (with TargNet
    syncs every 20) stay within 1e-5 of the oracle trajectory on BOTH the
    loss and every parameter."""
    ep, _ = _nets(jax.random.fold_in(KEY, 7))
    p_ref = p_ker = ep
    t_ref = t_ker = ep
    o_ref, o_ker = _adam_init(ep), _adam_init(ep)
    upd_ref = jax.jit(dqn_td_update)
    upd_ker = jax.jit(lambda e, t, o, b: dqn_td_update_fused(
        e, t, o, b, interpret=INTERPRET))
    max_l = max_p = 0.0
    for i in range(64):
        batch = _batch(jax.random.fold_in(KEY, 1000 + i), 32)
        p_ref, o_ref, l_ref = upd_ref(p_ref, t_ref, o_ref, batch)
        p_ker, o_ker, l_ker = upd_ker(p_ker, t_ker, o_ker, batch)
        if (i + 1) % 20 == 0:
            t_ref, t_ker = p_ref, p_ker
        max_l = max(max_l, abs(float(l_ref) - float(l_ker)))
        max_p = max(max_p, max(
            float(jnp.max(jnp.abs(a - b))) for a, b in zip(p_ref, p_ker)))
    assert max_l <= 1e-5, f"loss drifted {max_l:.2e}"
    assert max_p <= 1e-5, f"params drifted {max_p:.2e}"


def test_grads_under_vmap_dp_seam():
    """The DP trainer vmaps the grads half over per-lane batches and
    pmeans the result before a shared adam_apply; the kernel must
    reproduce that whole seam."""
    ep, tp = _nets(KEY)
    lanes, b = 4, 16
    batches = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[_batch(jax.random.fold_in(KEY, 50 + i), b) for i in range(lanes)])
    l_ref, g_ref = jax.vmap(
        lambda bt: dqn_td_grads(ep, tp, bt))(batches)
    l_ker, g_ker = jax.vmap(
        lambda bt: dqn_td_grads_fused(ep, tp, bt,
                                      interpret=INTERPRET))(batches)
    np.testing.assert_allclose(np.asarray(l_ker), np.asarray(l_ref),
                               rtol=1e-5, atol=1e-6)
    _assert_grads_close(
        jax.tree_util.tree_map(lambda g: g.mean(0), g_ref),
        jax.tree_util.tree_map(lambda g: g.mean(0), g_ker))
    # lane-averaged grads feed the same adam_apply on both sides
    opt = _adam_init(ep)
    pa, _ = adam_apply(ep, opt,
                       jax.tree_util.tree_map(lambda g: g.mean(0), g_ref))
    pb, _ = adam_apply(ep, opt,
                       jax.tree_util.tree_map(lambda g: g.mean(0), g_ker))
    _assert_grads_close(pa, pb)


def test_kernel_inside_jit_scan_cond():
    """The engine inlines the update inside lax.cond inside lax.scan —
    the kernel must trace and run there."""
    ep, tp = _nets(KEY)
    opt = _adam_init(ep)
    batch = _batch(jax.random.fold_in(KEY, 4), 32)

    @jax.jit
    def run(p, o):
        def body(carry, do):
            p, o = carry
            p2, o2, loss = jax.lax.cond(
                do,
                lambda _: dqn_td_update_fused(p, tp, o, batch,
                                              interpret=INTERPRET),
                lambda _: (p, o, jnp.float32(0.0)), None)
            return (p2, o2), loss
        return jax.lax.scan(body, (p, o),
                            jnp.array([True, False, True]))

    (p_f, o_f), losses = run(ep, opt)
    # two real updates, one skip
    assert int(o_f.step) == 2
    assert float(losses[1]) == 0.0 and float(losses[0]) > 0.0


@pytest.mark.parametrize("platform,interpret", [
    ("cpu", True),     # no Pallas compiler on the CPU backend
    ("tpu", False),    # Mosaic: the path every chip run takes
    ("gpu", False),
])
def test_protocol_interpret_decision_table(platform, interpret):
    """Pallas interprets if and only if the backend is the CPU."""
    from repro.compat import _interpret_for
    assert _interpret_for(platform) is interpret


# ---------------------------------------------------------------------------
# engine integration: ScanFlexAI(td_kernel=...)
# ---------------------------------------------------------------------------

def _engine_setup():
    from repro.core.environment import EnvironmentParams, build_task_queue
    from repro.core.flexai import FlexAIConfig
    from repro.core.hmai import HMAIPlatform
    q = build_task_queue(EnvironmentParams(
        route_km=0.06, rate_scale=0.05, seed=9, max_times_turn=2,
        max_times_reverse=1, max_duration_turn=4.0,
        max_duration_reverse=6.0))
    plat = HMAIPlatform(capacity_scale=0.05)
    cfg = FlexAIConfig(min_replay=32, batch_size=16, update_every=4,
                       target_sync_every=10, seed=3)
    return plat, cfg, q


def test_scanflexai_td_kernel_off_bit_identical():
    """td_kernel=False IS the default trainer: same compiled trace, so
    the episode trajectory must match bit-exactly."""
    from repro.core.flexai import ScanFlexAI
    plat, cfg, q = _engine_setup()
    t_def = ScanFlexAI(plat, cfg)
    t_off = ScanFlexAI(plat, cfg, td_kernel=False)
    t_def.train_episode(q)
    t_off.train_episode(q)
    for name, a, b in zip(t_def.ts.eval_p._fields, t_def.ts.eval_p,
                          t_off.ts.eval_p):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_scanflexai_td_kernel_default_trace_has_no_pallas():
    """The off switch must COMPILE OUT: the default episode jaxpr may not
    contain a pallas_call (the no-regression guarantee for the default
    path is structural, not just a timing)."""
    from repro.core.flexai.engine import make_train_fn, train_init
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import tasks_to_arrays
    plat, cfg, q = _engine_setup()
    spec = spec_from_platform(plat)
    ts = train_init(jax.random.PRNGKey(0), 3 + 5 * plat.n, plat.n,
                    cfg.replay_capacity)
    ta = tasks_to_arrays(q)
    jaxpr_off = jax.make_jaxpr(make_train_fn(spec, cfg))(ts, ta)
    assert "pallas_call" not in str(jaxpr_off)
    jaxpr_on = jax.make_jaxpr(
        make_train_fn(spec, cfg, td_kernel=True))(ts, ta)
    assert "pallas_call" in str(jaxpr_on)


def test_scanflexai_td_kernel_trains_at_parity():
    """The acceptance pin at the ScanFlexAI surface: a full fused episode
    (dozens of in-scan TD updates + TargNet syncs + greedy acting off the
    updated params) stays within 1e-5 of the default trainer on losses
    and final EvalNet params."""
    from repro.core.flexai import ScanFlexAI
    plat, cfg, q = _engine_setup()
    t_ref = ScanFlexAI(plat, cfg)
    t_ker = ScanFlexAI(plat, cfg, td_kernel=True)
    s_ref = t_ref.train_episode(q)
    s_ker = t_ker.train_episode(q)
    assert len(t_ref.losses) >= 30, "route too short to exercise updates"
    assert len(t_ker.losses) == len(t_ref.losses)
    np.testing.assert_allclose(np.asarray(t_ker.losses),
                               np.asarray(t_ref.losses),
                               rtol=1e-5, atol=1e-5)
    for name, a, b in zip(t_ref.ts.eval_p._fields, t_ref.ts.eval_p,
                          t_ker.ts.eval_p):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    assert s_ker["stm_rate"] == pytest.approx(s_ref["stm_rate"], abs=1e-6)


def test_scanflexai_td_kernel_dp_path():
    """DP trainer (shared agent, per-lane grads + mean + shared Adam)
    with the kernel grads variant walks the oracle DP trajectory."""
    from repro.core.flexai import ScanFlexAI
    plat, cfg, q = _engine_setup()
    t_ref = ScanFlexAI(plat, cfg, lanes=2, dp=True)
    t_ker = ScanFlexAI(plat, cfg, lanes=2, dp=True, td_kernel=True)
    t_ref.train_episode([q, q])
    t_ker.train_episode([q, q])
    assert len(t_ref.losses) >= 10
    assert len(t_ker.losses) == len(t_ref.losses)
    np.testing.assert_allclose(np.asarray(t_ker.losses),
                               np.asarray(t_ref.losses),
                               rtol=1e-5, atol=1e-5)
    for name, a, b in zip(t_ref.ts.eval_p._fields, t_ref.ts.eval_p,
                          t_ker.ts.eval_p):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
