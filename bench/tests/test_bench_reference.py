"""The plain reference against the program's scan engine, and the
control's arithmetic."""
import benchtest
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib.registry import Registry
from benchlib.traffic import Mix


def _serve_small(seed):
    """Greedy placements of the small mix's windows by the program's
    segment function, one request per lane."""
    from repro.core.flexai.dqn import DQNParams
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import platform_init, stack_states
    from repro.core.tasks import TaskArrays, stack_task_arrays
    from repro.serve.qos import _segment_fn
    reg = Registry()
    cfg = reg.config("hmai_fifo_serve")
    drv = reg.driver(cfg["driver"])
    # full camera rate, so the platform queues and verdicts vary
    wins = Mix.from_dict("s", dict(benchtest.SMALL_MIX, rate_scale=1.0,
                                   window_s=0.25)).windows()[:24]
    plat = HMAIPlatform()
    params = DQNParams(*drv.make_params(seed, drv.qnet_widths(cfg)))
    from repro.core.platform_jax import spec_from_platform
    seg = _segment_fn(spec_from_platform(plat), 1.0)
    batch = stack_task_arrays([TaskArrays(w.kind, w.arrival, w.safety,
                                          w.group, np.ones(w.n, bool))
                               for w in wins])
    state = stack_states([platform_init(plat.n)] * len(wins))
    _, recs = seg(params, batch, state)
    return cfg, reg, wins, params, jax.device_get(recs)


@pytest.mark.parametrize("seed", [3, 2**40 + 7])
def test_reference_agrees_with_the_engine(seed):
    cfg, reg, wins, params, recs = _serve_small(seed)
    ref_mod = reg.reference(cfg["reference"])
    T = recs.action.shape[1]
    kind = np.zeros((len(wins), T), np.int32)
    arr = np.zeros((len(wins), T), np.float32)
    safe = np.ones((len(wins), T), np.float32)
    for i, w in enumerate(wins):
        kind[i, :w.n], arr[i, :w.n], safe[i, :w.n] = w.kind, w.arrival, \
            w.safety
    n = np.array([w.n for w in wins])
    out = ref_mod.replay(ref_mod.tables(cfg), [np.asarray(p) for p in params],
                         kind, arr, safe, n, recs.action, control=True)
    live = np.arange(T)[None] < n[:, None]
    assert out["gap"][live].max() < 1e-5
    assert np.array_equal(out["met32"][live], np.asarray(recs.met)[live])
    # the control departs: its bfloat16 observation flips placements
    assert ((out["ctrl_gap"] > 0) | (out["ctrl_met"] != out["met32"]))[
        live].sum() > 0


def test_tables_equal_the_programs_platform():
    from repro.core.hmai import HMAIPlatform
    from repro.core.platform_jax import kind_feature_table
    reg = Registry()
    tab = reg.reference("hmai_placement").tables(
        reg.config("hmai_fifo_serve"))
    plat = HMAIPlatform()
    assert np.allclose(tab["exec"], plat.exec_time_table, rtol=1e-12)
    assert np.allclose(tab["energy"], plat.energy_table, rtol=1e-12)
    assert np.allclose(tab["feat"], kind_feature_table(), rtol=1e-6)


def test_bf16_rounding_and_high_matmul():
    ref = Registry().reference("hmai_placement")
    x = np.random.default_rng(0).normal(size=(64, 58)).astype(np.float32)
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    assert np.array_equal(ref.to_bf16(x), want)
    w = np.random.default_rng(1).normal(size=(58, 256)).astype(np.float32)
    exact = x.astype(np.float64) @ w.astype(np.float64)
    err_high = np.abs(ref.matmul_high(x, w) - exact).max()
    err_bf16 = np.abs(ref.to_bf16(x) @ ref.to_bf16(w) - exact).max()
    assert err_high < 1e-2 * err_bf16
