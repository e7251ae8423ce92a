"""Property-based tests (hypothesis) on system invariants.

``hypothesis`` is an optional dev dependency (requirements-dev.txt); the
module skips cleanly when it isn't installed so ``pytest -x -q`` still
collects the rest of the suite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.criteria import (gvalue, matching_score_det,
                                 matching_score_tra, rss_safe_distance,
                                 rss_safety_time)
from repro.core.hmai import HMAIPlatform
from repro.core.tasks import Task, TaskKind
from repro.sharding import logical_to_mesh_axes
from repro.train.compression import (compress_grads_int8_ef, dequantize_int8,
                                     ef_init, quantize_int8)

SETTINGS = settings(max_examples=60, deadline=None)


# ---------------------------------------------------------------------------
# RSS / criteria
# ---------------------------------------------------------------------------

@SETTINGS
@given(d=st.floats(30.0, 500.0), v1=st.floats(1.0, 40.0),
       v2=st.floats(0.0, 40.0))
def test_rss_roundtrip(d, v1, v2):
    """safety_time inverts safe_distance whenever a positive budget exists."""
    rho = rss_safety_time(d, v1, v2)
    assert rho >= 0.0
    if rho > 0:
        np.testing.assert_allclose(rss_safe_distance(v1, v2, rho), d,
                                   rtol=1e-6)


@SETTINGS
@given(d=st.floats(30.0, 500.0), v=st.floats(1.0, 40.0),
       dv=st.floats(0.1, 10.0))
def test_rss_monotonic_in_speed(d, v, dv):
    """Faster closing speed -> strictly less response budget."""
    assert rss_safety_time(d, v + dv, v + dv) <= rss_safety_time(d, v, v)


@SETTINGS
@given(t=st.floats(0.0, 10.0), s=st.floats(0.01, 10.0))
def test_matching_score_bounds(t, s):
    ms_det = matching_score_det(t, s)
    ms_tra = matching_score_tra(t, s)
    assert -1.0 <= ms_det <= 1.0
    assert ms_tra in (-1.0, 1.0)
    if t > s:
        assert ms_det == -1.0 and ms_tra == -1.0


@SETTINGS
@given(e=st.floats(0.0, 100.0), t=st.floats(0.0, 100.0),
       r=st.floats(0.0, 1.0), de=st.floats(0.01, 10.0))
def test_gvalue_monotonicity(e, t, r, de):
    """More energy or time strictly lowers Gvalue; more balance raises it."""
    base = gvalue(e, t, r, e_scale=100.0, t_scale=100.0)
    assert gvalue(e + de, t, r, e_scale=100.0, t_scale=100.0) < base
    assert gvalue(e, t + de, r, e_scale=100.0, t_scale=100.0) < base
    if r + 0.01 <= 1.0:
        assert gvalue(e, t, r + 0.01, e_scale=100.0, t_scale=100.0) > base


# ---------------------------------------------------------------------------
# Platform simulator
# ---------------------------------------------------------------------------

@SETTINGS
@given(assignments=st.lists(st.integers(0, 10), min_size=1, max_size=40),
       seed=st.integers(0, 1000))
def test_platform_invariants(assignments, seed):
    """Response >= exec time; per-accelerator time monotone; energy adds up."""
    rng = np.random.default_rng(seed)
    plat = HMAIPlatform()
    t = 0.0
    total_e = 0.0
    for uid, a in enumerate(assignments):
        t += float(rng.uniform(0, 0.01))
        kind = [TaskKind.YOLO, TaskKind.SSD, TaskKind.GOTURN][uid % 3]
        task = Task(uid=uid, kind=kind, camera_group="FC", camera_id=0,
                    arrival_time=t, safety_time=1.0)
        rec = plat.execute(task, a % plat.n)
        assert rec.response_time >= rec.exec_time - 1e-12
        assert rec.finish >= rec.start
        assert rec.wait >= 0.0
        total_e += rec.energy
    np.testing.assert_allclose(plat.total_energy, total_e, rtol=1e-9)
    assert 0.0 <= plat.r_balance <= 1.0 + 1e-9


# ---------------------------------------------------------------------------
# Compression
# ---------------------------------------------------------------------------

@SETTINGS
@given(vals=st.lists(st.floats(-100.0, 100.0, allow_nan=False),
                     min_size=1, max_size=64))
def test_int8_quantize_error_bound(vals):
    x = jnp.asarray(vals, jnp.float32)
    q, scale = quantize_int8(x)
    err = jnp.max(jnp.abs(dequantize_int8(q, scale) - x))
    assert float(err) <= float(scale) * 0.5 + 1e-6


def test_error_feedback_compensates():
    """Sum of EF-compressed grads converges to sum of true grads."""
    rng = np.random.default_rng(0)
    g_true = [jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
              for _ in range(50)]
    ef = ef_init({"w": g_true[0]})
    applied = jnp.zeros((8, 8))
    for g in g_true:
        out, ef = compress_grads_int8_ef({"w": g}, ef)
        applied = applied + out["w"]
    total_true = sum(g_true)
    resid = float(jnp.max(jnp.abs(applied + ef["w"] - total_true)))
    assert resid < 1e-3  # applied + residual == true sum (EF identity)


# ---------------------------------------------------------------------------
# Sharding rules
# ---------------------------------------------------------------------------

@SETTINGS
@given(names=st.lists(st.sampled_from(
    ["batch", "embed", "heads", "mlp", "vocab", "expert", None]),
    min_size=1, max_size=4))
def test_mesh_axes_never_reused(names):
    from jax.sharding import AbstractMesh

    from repro.sharding import DEFAULT_RULES
    mesh = AbstractMesh((2, 2), ("data", "model"))
    spec = logical_to_mesh_axes(tuple(names), DEFAULT_RULES, mesh)
    used = []
    for entry in spec:
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        used.extend(entries)
    assert len(used) == len(set(used)), spec


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------

@SETTINGS
@given(seed=st.integers(0, 100))
def test_moe_capacity_and_gates(seed):
    from repro.models.config import ModelConfig
    from repro.models.moe import init_moe, moe_apply, _capacity
    from repro.sharding import unbox
    cfg = ModelConfig(name="pm", family="moe", num_layers=1, d_model=16,
                      num_heads=2, num_kv_heads=2, d_ff=16, vocab_size=32,
                      num_experts=4, num_experts_per_token=2)
    key = jax.random.PRNGKey(seed)
    p = unbox(init_moe(key, cfg, jnp.float32))
    x = jax.random.normal(key, (2, 8, 16))
    out, aux = moe_apply(p, cfg, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) >= 0.0
    assert _capacity(cfg, 16) >= 8
