"""Sharding layer: logical-axis resolution (in-process) + an 8-device
subprocess check that a sharded train step runs and matches single-device
results (the dry-run proper covers the 512-device meshes)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.sharding import (DEFAULT_RULES, Param, boxed_axes,
                            logical_to_mesh_axes, unbox)


def test_param_boxing_roundtrip():
    import jax.numpy as jnp
    p = {"a": Param(jnp.ones((4, 8)), ("embed", "mlp")),
         "b": {"c": Param(jnp.zeros((3,)), ("unsharded",))}}
    values = unbox(p)
    axes = boxed_axes(p)
    assert values["a"].shape == (4, 8)
    assert axes["a"] == ("embed", "mlp")
    assert axes["b"]["c"] == ("unsharded",)


def test_eval_shape_keeps_boxes():
    import jax.numpy as jnp

    def init():
        return {"w": Param(jnp.zeros((8, 16)), ("embed", "mlp"))}

    shapes = jax.eval_shape(init)
    assert isinstance(shapes["w"], Param)
    assert shapes["w"].value.shape == (8, 16)
    assert shapes["w"].axes == ("embed", "mlp")


def test_multipod_axis_resolution():
    mesh = AbstractMesh((2, 4, 4), ("pod", "data", "model"))
    spec = logical_to_mesh_axes(("batch", None, "mlp"), DEFAULT_RULES, mesh)
    assert spec[0] == ("pod", "data")
    assert spec[2] == "model"
    # single-pod mesh: the "pod" component is dropped transparently
    mesh1 = AbstractMesh((4, 4), ("data", "model"))
    spec1 = logical_to_mesh_axes(("batch", None, "mlp"), DEFAULT_RULES, mesh1)
    assert spec1[0] == "data"


_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_test_mesh
    from repro.models.api import model_api
    from repro.sharding import activate, tree_shardings, unbox, Param
    from repro.train.loop import TrainHyper, init_train_state, make_train_step, train_state_boxed

    cfg = get_smoke_config("h2o-danube-3-4b")
    api = model_api(cfg)
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
        "loss_mask": jnp.ones((8, 32), jnp.float32),
    }
    hyper = TrainHyper(warmup_steps=1, total_steps=10)
    step = make_train_step(api, hyper)

    # single device
    params = unbox(api.init(key))
    state = init_train_state(params, hyper)
    _, m1 = jax.jit(step)(state, batch)
    loss1 = float(m1["loss"])

    # 2x4 mesh, sharded state
    mesh = make_test_mesh((2, 4), ("data", "model"))
    boxed = jax.eval_shape(api.init, key)
    boxed_state = train_state_boxed(boxed, hyper)
    shardings = tree_shardings(boxed_state, mesh)
    with activate(mesh):
        params2 = unbox(api.init(key))
        state2 = init_train_state(params2, hyper)
        state2 = jax.device_put(state2, shardings)
        jitted = jax.jit(step, in_shardings=(shardings, None))
        new_state, m2 = jitted(state2, batch)
        loss2 = float(m2["loss"])
    print(json.dumps({"loss1": loss1, "loss2": loss2}))
""")


def test_sharded_step_matches_single_device(tmp_path):
    script = tmp_path / "sharded_check.py"
    script.write_text(_SUBPROCESS_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(res["loss1"], res["loss2"], rtol=2e-2)
