"""Distribution extras: explicit-EP MoE equivalence (subprocess, 8 devices),
HLO collective parser, decode-rules structure, virtual platform."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np


def _run_sub(script: str, timeout=420):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


def test_moe_shard_map_matches_gspmd():
    """Explicit all-to-all EP == GSPMD scatter MoE (fwd bit-exact, grads)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from repro.models.config import ModelConfig
        from repro.models.moe import init_moe, moe_apply_gspmd, moe_apply_shard_map
        from repro.sharding import activate, unbox
        from repro.launch.mesh import make_test_mesh
        cfg = ModelConfig(name="sm", family="moe", num_layers=1, d_model=32,
                          num_heads=2, num_kv_heads=2, d_ff=24, vocab_size=64,
                          num_experts=8, num_experts_per_token=2,
                          moe_capacity_factor=8.0)
        key = jax.random.PRNGKey(0)
        p = unbox(init_moe(key, cfg, jnp.float32))
        x = jax.random.normal(key, (4, 16, 32))
        ref, _ = jax.jit(lambda p, x: moe_apply_gspmd(p, cfg, x))(p, x)
        mesh = make_test_mesh((2, 4), ("data", "model"))
        with activate(mesh):
            out, _ = jax.jit(lambda p, x: moe_apply_shard_map(p, cfg, x, mesh))(p, x)
            g1 = jax.jit(jax.grad(lambda p, x: jnp.sum(
                moe_apply_shard_map(p, cfg, x, mesh)[0] ** 2)))(p, x)
        g2 = jax.jit(jax.grad(lambda p, x: jnp.sum(
            moe_apply_gspmd(p, cfg, x)[0] ** 2)))(p, x)
        fwd_err = float(jnp.max(jnp.abs(out - ref)))
        g_err = max(float(jnp.max(jnp.abs(g1[k] - g2[k])))
                    for k in ("wi_gate", "wo", "router"))
        print(f"RESULT {fwd_err} {g_err}")
    """)
    out = _run_sub(script)
    line = [l for l in out.splitlines() if l.startswith("RESULT")][0]
    fwd_err, g_err = map(float, line.split()[1:])
    assert fwd_err < 1e-5, fwd_err
    assert g_err < 1e-3, g_err


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
      %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %p), dims={0}
      %ar = f32[64]{0} all-reduce(f32[64]{0} %q), to_apply=%add
      %a2a = f32[16,32]{1,0} all-to-all(f32[16,32]{1,0} %r), dims={0}
      %dot = f32[8,8]{1,0} dot(f32[8,4]{1,0} %a, f32[4,8]{1,0} %b)
    """
    res = parse_collectives(hlo)
    assert res["all-gather"]["count"] == 1
    assert res["all-gather"]["operand_bytes"] == 1 * 128 * 2
    assert res["all-reduce"]["operand_bytes"] == 64 * 4
    assert res["all-to-all"]["count"] == 1
    assert res["total_count"] == 3  # the dot is not a collective


def test_decode_rules_structure():
    from repro.sharding.partition import DECODE_RULES, DEFAULT_RULES
    d = dict(DECODE_RULES)
    assert d["embed"] is None          # no FSDP weight gathers at decode
    assert d["mlp"] == ("model", "data")
    assert d["cache_batch"] == ("pod", "data")
    assert dict(DEFAULT_RULES)["embed"] == "data"  # training keeps FSDP


def test_virtual_platform_schedules():
    import jax
    import pytest

    from repro.core.virtual_platform import DEFAULT_POOLS, VirtualPlatform
    from repro.core.tasks import Task, TaskKind
    n_dev = len(jax.devices())
    if n_dev < len(DEFAULT_POOLS):
        # pools never share a device: too few devices is an error
        with pytest.raises(ValueError, match="pools ask for"):
            VirtualPlatform(run_real=False)
    pools = DEFAULT_POOLS[:n_dev]
    plat = VirtualPlatform(pool_specs=pools, run_real=False)
    assert plat.n == len(pools)
    assert [p.devices for p in plat.pools] == [
        [d] for d in jax.devices()[:len(pools)]]
    assert all(p.measured_fps for p in plat.pools)
    rec = plat.execute(Task(uid=0, kind=TaskKind.YOLO, camera_group="FC",
                            camera_id=0, arrival_time=0.0, safety_time=5.0), 0)
    assert rec.exec_time > 0
    spec = plat.pools[0].as_accelerator_spec()
    assert spec.arch.name == "MconvMC"
