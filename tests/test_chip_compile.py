"""Compiles for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX and compiles for a described
``v5e:2x2`` topology without a chip: what Mosaic or XLA would refuse on the
chip (block shapes off the (8, 128) tiling, VMEM overflow, a program that
cannot be partitioned) is refused here, at no chip time.  Nothing runs, so
these tests say nothing about results or speed — the interpret-mode parity
tests and ``chip_smoke.py`` cover those.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under several pytest
workers every worker imports this file but only the one given it compiles.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.flexai.dqn import _adam_init, init_qnet
from repro.core.hmai import HMAIPlatform
from repro.core.platform_jax import platform_init, spec_from_platform, stack_states
from repro.core.tasks import invalid_task_arrays, stack_task_arrays
from repro.models.perception.nets import SSD_WIDTH, YOLO_WIDTH
from repro.serve.qos import QoSConfig

N_CORES = HMAIPlatform().n              # 11 accelerators in HMAI (Table 8)
STATE_DIM = 3 + 5 * N_CORES             # FlexAI state vector
BATCH = 64                              # FlexAIConfig.batch_size

# 3x3 convs of the perception nets at their spec widths on a 416-pixel
# input: YOLO DarkNet blocks of stage 256 at 52x52 (102 -> 204 channels)
# and of stage 512 at 26x26 (204 -> 409, several channel steps in SconvOD
# and MconvMC), and the SSD ResNet stage-256 entry at 104x104 (54 -> 217)
CONV_LAYERS = {
    "yolo_52": (52, int(128 * YOLO_WIDTH), int(256 * YOLO_WIDTH)),
    "yolo_26": (26, int(256 * YOLO_WIDTH), int(512 * YOLO_WIDTH)),
    "ssd_104": (104, int(64 * SSD_WIDTH), int(256 * SSD_WIDTH)),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.asarray(a).dtype,
                                       sharding=sharding), tree)


def _td_inputs(sharding):
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_qnet(key, STATE_DIM, N_CORES))
    batch = {"s": np.zeros((BATCH, STATE_DIM), np.float32),
             "a": np.zeros((BATCH,), np.int32),
             "r": np.zeros((BATCH,), np.float32),
             "s_next": np.zeros((BATCH, STATE_DIM), np.float32),
             "done": np.zeros((BATCH,), np.float32)}
    p = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        params)
    opt = jax.eval_shape(_adam_init, params)
    o = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        opt)
    return p, o, _shapes(batch, sharding)


@pytest.mark.parametrize("variant", ["grads", "update"])
def test_td_kernel_compiles_for_v5e(variant, one_chip, no_persistent_cache):
    from repro.kernels.dqn_update import (dqn_td_grads_fused,
                                          dqn_td_update_fused)
    p, o, b = _td_inputs(one_chip)
    if variant == "grads":
        fn = jax.jit(lambda e, t, bb: dqn_td_grads_fused(
            e, t, bb, interpret=False))
        compiled = fn.lower(p, p, b).compile()
    else:
        fn = jax.jit(lambda e, t, oo, bb: dqn_td_update_fused(
            e, t, oo, bb, interpret=False))
        compiled = fn.lower(p, p, o, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layer", sorted(CONV_LAYERS))
@pytest.mark.parametrize("dataflow", ["SconvOD", "SconvIC", "MconvMC"])
def test_conv_dataflow_compiles_for_v5e(dataflow, layer, one_chip,
                                        no_persistent_cache):
    from repro.kernels.conv_dataflow import conv2d
    hw, cin, cout = CONV_LAYERS[layer]
    x = jax.ShapeDtypeStruct((1, hw, hw, cin), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, cin, cout), jnp.float32,
                             sharding=one_chip)
    compiled = jax.jit(lambda a, b: conv2d(
        a, b, dataflow=dataflow, padding="SAME", interpret=False)
    ).lower(x, w).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _segment_inputs(slots, chunk, p_sharding, lane_sharding):
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda: init_qnet(key, STATE_DIM, N_CORES))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                       sharding=p_sharding), params)
    tasks = stack_task_arrays([invalid_task_arrays(chunk)] * slots)
    state = stack_states([platform_init(N_CORES)] * slots)
    return (params, _shapes(tasks, lane_sharding),
            _shapes(state, lane_sharding))


@pytest.mark.parametrize("cols", ["chunk", 2048])
def test_qos_segment_compiles_for_v5e(cols, one_chip, no_persistent_cache):
    """The serving hot path: one ``slots x chunk`` scan segment of the
    FlexAI greedy engine over the real HMAI spec, and the one call that
    serves half a 4096-task FIFO wave."""
    from repro.serve.qos import _segment_fn
    cfg = QoSConfig()
    seg = _segment_fn(spec_from_platform(HMAIPlatform()), 1.0)
    args = _segment_inputs(cfg.slots, cfg.chunk if cols == "chunk" else cols,
                           one_chip, one_chip)
    compiled = seg.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_qos_segment_sharded_compiles_for_v5e_2x2(topo,
                                                  no_persistent_cache):
    """The same segment shard_mapped over the four described chips on a
    ("routes",) mesh, one wave lane per chip."""
    from repro.compat import make_mesh
    from repro.serve.qos import _segment_fn
    cfg = QoSConfig()
    mesh = make_mesh((4,), ("routes",), devices=topo.devices)
    seg = _segment_fn(spec_from_platform(HMAIPlatform()), 1.0, mesh=mesh)
    args = _segment_inputs(cfg.slots, cfg.chunk, NamedSharding(mesh, P()),
                           NamedSharding(mesh, P("routes")))
    compiled = seg.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_train_episode_with_td_kernel_compiles_for_v5e(one_chip, monkeypatch,
                                                       no_persistent_cache):
    """The training cell's device program: one fused FlexAI episode of
    32,768 steps with the compiled TD-update kernel inside the scan's
    ``lax.cond``, at the launcher's batch 64 and replay ring of 50,000."""
    from repro.core.flexai.engine import make_train_fn, train_init
    from repro.core.tasks import invalid_task_arrays
    from repro.kernels.dqn_update import ops
    from repro.launch.train import build_flexai_trainer
    monkeypatch.setattr(ops, "pallas_interpret_default", lambda: False)
    cfg = build_flexai_trainer(td_kernel=True).cfg
    fn = make_train_fn(spec_from_platform(HMAIPlatform()), cfg,
                       td_kernel=True)
    ts = jax.eval_shape(lambda: train_init(
        jax.random.PRNGKey(0), STATE_DIM, N_CORES, cfg.replay_capacity))
    tasks = jax.eval_shape(lambda: invalid_task_arrays(32768))
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    compiled = fn.lower(on_chip(ts), on_chip(tasks)).compile()
    text = compiled.as_text()
    assert "HloModule jit_train_episode" in text
    assert "dqn_td_update" in text and "tpu_custom_call" in text
