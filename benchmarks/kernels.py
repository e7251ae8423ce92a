"""Kernel-layer honesty benchmark -> BENCH_kernels.json.

The kernel layer's standing risk is *silent* untruth: interpret-mode
parity quietly standing in for hardware numbers, or the fused TD kernel
regressing the default trainer it is supposed to leave untouched.  This
module makes each claim explicit and machine-checkable:

1. **Interpret parity** (always, gating): every Pallas kernel in the
   repo — the three conv dataflows, flash attention, the SSD scan, and
   both fused TD-update variants — runs in interpret mode against its
   oracle at a fixed tolerance.
2. **TD trajectory pin** (always, gating): 64 consecutive fused updates
   track ``dqn_td_update`` to <= 1e-5 on loss and every parameter.
3. **CPU trainer no-regression** (always, gating): the default
   (``td_kernel=False``) training episode must contain NO pallas_call in
   its jaxpr and must produce a jaxpr identical to the pre-seam trainer
   (structural no-regression — stronger than a timing, immune to machine
   noise); a timing of both paths is recorded for the humans.
4. **Compiled microbenchmark** (any backend but the CPU): the same
   kernels timed non-interpret vs their XLA oracles.  On the CPU backend
   this leg records an explicit ``skipped`` reason — it never silently
   greens.
5. **Interpret-mode trainer throughput** (report only): the honest
   number for what ``td_kernel=True`` costs on a CPU host, where the
   kernel body runs as unfused interpreted ops.

Host tuning env is stamped into the JSON (benchmarks.common).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

PARITY_TOL = 1e-4   # conv/attention/ssd f32 (existing test-suite tol)
TD_TOL = 1e-5       # the ISSUE-9 acceptance pin


# ---------------------------------------------------------------------------
# leg 1: interpret parity across every kernel
# ---------------------------------------------------------------------------

def _interpret_parity(interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.flexai.dqn import (_adam_init, dqn_td_grads,
                                       dqn_td_update, init_qnet)
    from repro.kernels.conv_dataflow import conv2d, conv2d_ref
    from repro.kernels.dqn_update import (dqn_td_grads_fused,
                                          dqn_td_update_fused)
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.ssd_scan import ssd_ref, ssd_scan

    key = jax.random.PRNGKey(0)
    out = {}

    def record(name, err, tol):
        out[name] = {"max_err": float(err), "tol": tol,
                     "ok": bool(err <= tol)}

    # conv dataflows (incl. a prime-ho / prime-cin shape so the padded
    # tile paths are what gets gated, not just the divisible fast path)
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (2, 15, 10, 11), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 11, 8), jnp.float32) * 0.2
    ref = conv2d_ref(x, w)
    for df in ("SconvOD", "SconvIC", "MconvMC"):
        o = conv2d(x, w, dataflow=df, interpret=interpret)
        record(f"conv/{df}", jnp.max(jnp.abs(o - ref)), PARITY_TOL)

    # flash attention
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), jnp.float32)
    kk = jax.random.normal(ks[1], (1, 64, 4, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 64, 4, 32), jnp.float32)
    o = flash_attention(q, kk, v, causal=True, block_q=32, block_k=32,
                        interpret=interpret)
    import math
    qf = q.transpose(0, 2, 1, 3).reshape(4, 64, 32)
    kf = kk.transpose(0, 2, 1, 3).reshape(4, 64, 32)
    vf = v.transpose(0, 2, 1, 3).reshape(4, 64, 32)
    aref = attention_ref(qf, kf, vf, causal=True,
                         scale=1 / math.sqrt(32))
    aref = aref.reshape(1, 4, 64, 32).transpose(0, 2, 1, 3)
    record("flash_attention", jnp.max(jnp.abs(o - aref)), PARITY_TOL)

    # ssd scan
    ks = jax.random.split(key, 4)
    u = jax.random.normal(ks[0], (1, 32, 2, 8), jnp.float32) * 0.3
    a = -jnp.abs(jax.random.normal(ks[1], (1, 32, 2))) * 0.2
    Bm = jax.random.normal(ks[2], (1, 32, 4), jnp.float32) * 0.5
    Cm = jax.random.normal(ks[3], (1, 32, 4), jnp.float32) * 0.5
    y, _ = ssd_scan(u, a, Bm, Cm, chunk=8, interpret=interpret)
    uf = u.transpose(0, 2, 1, 3).reshape(2, 32, 8)
    af = a.transpose(0, 2, 1).reshape(2, 32)
    Bf = jnp.repeat(Bm[:, None], 2, 1).reshape(2, 32, 4)
    Cf = jnp.repeat(Cm[:, None], 2, 1).reshape(2, 32, 4)
    yr, _ = ssd_ref(uf, af, Bf, Cf)
    yr = yr.reshape(1, 2, 32, 8).transpose(0, 2, 1, 3)
    record("ssd_scan", jnp.max(jnp.abs(y - yr)), PARITY_TOL)

    # fused TD update, both variants (B=40, tile=16 -> masked tail block)
    D, A = 18, 3
    ep = init_qnet(key, D, A)
    tp = init_qnet(jax.random.fold_in(key, 9), D, A)
    ks = jax.random.split(key, 5)
    batch = {"s": jax.random.normal(ks[0], (40, D)),
             "a": jax.random.randint(ks[1], (40,), 0, A),
             "r": jax.random.normal(ks[2], (40,)) * 3,
             "s_next": jax.random.normal(ks[3], (40, D)),
             "done": (jax.random.uniform(ks[4], (40,)) < 0.2)
             .astype(jnp.float32)}
    l0, g0 = dqn_td_grads(ep, tp, batch)
    l1, g1 = dqn_td_grads_fused(ep, tp, batch, batch_tile=16,
                                interpret=interpret)
    err = max(abs(float(l0) - float(l1)),
              max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(g0, g1)))
    record("dqn_td_grads", err, TD_TOL)
    opt = _adam_init(ep)
    p0, o0, ul0 = dqn_td_update(ep, tp, opt, batch)
    p1, o1, ul1 = dqn_td_update_fused(ep, tp, opt, batch, batch_tile=16,
                                      interpret=interpret)
    err = max(abs(float(ul0) - float(ul1)),
              max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(p0, p1)),
              max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(o0.mu, o1.mu)))
    record("dqn_td_update", err, TD_TOL)
    out["all_ok"] = all(v["ok"] for k, v in out.items() if k != "all_ok")
    return out


# ---------------------------------------------------------------------------
# leg 2: TD trajectory pin (the ISSUE-9 acceptance criterion)
# ---------------------------------------------------------------------------

def _td_trajectory(updates: int, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core.flexai.dqn import _adam_init, dqn_td_update, init_qnet
    from repro.kernels.dqn_update import dqn_td_update_fused

    key = jax.random.PRNGKey(77)
    D, A, B = 18, 3, 32
    ep = init_qnet(key, D, A)
    p_ref = p_ker = ep
    t_ref = t_ker = ep
    o_ref, o_ker = _adam_init(ep), _adam_init(ep)
    upd_ref = jax.jit(dqn_td_update)
    upd_ker = jax.jit(lambda e, t, o, b: dqn_td_update_fused(
        e, t, o, b, interpret=interpret))
    max_l = max_p = 0.0
    for i in range(updates):
        ks = jax.random.split(jax.random.fold_in(key, i), 5)
        batch = {"s": jax.random.normal(ks[0], (B, D)),
                 "a": jax.random.randint(ks[1], (B,), 0, A),
                 "r": jax.random.normal(ks[2], (B,)) * 2,
                 "s_next": jax.random.normal(ks[3], (B, D)),
                 "done": (jax.random.uniform(ks[4], (B,)) < 0.1)
                 .astype(jnp.float32)}
        p_ref, o_ref, l_ref = upd_ref(p_ref, t_ref, o_ref, batch)
        p_ker, o_ker, l_ker = upd_ker(p_ker, t_ker, o_ker, batch)
        if (i + 1) % 20 == 0:
            t_ref, t_ker = p_ref, p_ker
        max_l = max(max_l, abs(float(l_ref) - float(l_ker)))
        max_p = max(max_p, max(
            float(jnp.max(jnp.abs(a - b))) for a, b in zip(p_ref, p_ker)))
    return {"updates": updates, "max_loss_diff": max_l,
            "max_param_diff": max_p, "tol": TD_TOL,
            "ok": bool(max_l <= TD_TOL and max_p <= TD_TOL)}


# ---------------------------------------------------------------------------
# leg 3: default-path no-regression + report-only trainer timings
# ---------------------------------------------------------------------------

def _trainer_no_regression(tasks: int) -> dict:
    import jax

    from benchmarks.common import platform, timer
    from repro.core.flexai import FlexAIConfig
    from repro.core.flexai.engine import make_train_fn, train_init
    from repro.core.platform_jax import spec_from_platform
    from repro.core.tasks import tasks_to_arrays
    from benchmarks.training_throughput import _routes

    plat = platform()
    spec = spec_from_platform(plat)
    cfg = FlexAIConfig(lr=1e-3, gamma=0.98, batch_size=32, min_replay=64,
                       update_every=2, eps_decay_steps=2000,
                       target_sync_every=200, replay_capacity=4096, seed=7)
    state_dim = 3 + 5 * plat.n
    ta = tasks_to_arrays(_routes(1, tasks)[0])
    ts0 = train_init(jax.random.PRNGKey(cfg.seed), state_dim, plat.n,
                     cfg.replay_capacity)

    # structural no-regression: the default trace is pallas-free and the
    # explicit off-switch trace is IDENTICAL to it, so td_kernel=False
    # cannot cost anything by construction.  jvp_jaxpr_thunk params print
    # as `<function ... at 0x...>` — normalize the addresses, they are
    # per-trace closure identities, not structure.
    import re

    def trace(**kw):
        s = str(jax.make_jaxpr(make_train_fn(spec, cfg, **kw))(ts0, ta))
        return re.sub(r"0x[0-9a-f]+", "0x0", s)

    jaxpr_default = trace()
    jaxpr_off = trace(td_kernel=False)
    jaxpr_on = trace(td_kernel=True)
    pallas_free = "pallas_call" not in jaxpr_default
    off_identical = jaxpr_off == jaxpr_default
    on_has_kernel = "pallas_call" in jaxpr_on

    # timings (reported for humans; the gate is the structural check)
    fn_off = make_train_fn(spec, cfg)
    fn_on = make_train_fn(spec, cfg, td_kernel=True)
    _, t_off = timer(
        lambda: jax.block_until_ready(fn_off(ts0, ta)[0].eval_p), iters=3)
    _, t_on = timer(
        lambda: jax.block_until_ready(fn_on(ts0, ta)[0].eval_p), iters=3)
    return {
        "tasks": tasks,
        "default_pallas_free": bool(pallas_free),
        "off_jaxpr_identical_to_default": bool(off_identical),
        "on_jaxpr_has_pallas_call": bool(on_has_kernel),
        "off_env_steps_per_s": round(tasks / t_off, 1),
        "on_env_steps_per_s": round(tasks / t_on, 1),
        "on_vs_off_ratio": round(t_off / t_on, 3),
        "ok": bool(pallas_free and off_identical and on_has_kernel),
        "note": "the on-path number is interpret-mode Pallas executing "
                "the kernel body as plain XLA ops on CPU — it says "
                "nothing about hardware kernel speed in either "
                "direction; the compiled ratio is only measured on "
                "accelerator hardware (see the compiled leg / its skip "
                "reason), so this ratio is reported, never gated",
    }


# ---------------------------------------------------------------------------
# leg 4: compiled microbenchmark (hardware only — explicit skip otherwise)
# ---------------------------------------------------------------------------

def _compiled_leg(quick: bool) -> dict:
    from repro.kernels.protocol import compiled_available, status
    if not compiled_available():
        reason = ("CPU backend — Pallas runs interpreted, so no compiled "
                  "kernel exists to time; interpret parity above is the "
                  "only claim made")
        return {"skipped": True, "reason": reason, "protocol": status()}

    # hardware run: parity AND timing, non-interpret
    import jax

    from benchmarks.common import timer
    import jax.numpy as jnp
    from repro.core.flexai.dqn import _adam_init, dqn_td_update, init_qnet
    from repro.kernels.dqn_update import dqn_td_update_fused

    parity = _interpret_parity(interpret=False)
    key = jax.random.PRNGKey(5)
    D, A, B = 18, 3, 128
    ep = init_qnet(key, D, A)
    tp = init_qnet(jax.random.fold_in(key, 1), D, A)
    opt = _adam_init(ep)
    ks = jax.random.split(key, 5)
    batch = {"s": jax.random.normal(ks[0], (B, D)),
             "a": jax.random.randint(ks[1], (B,), 0, A),
             "r": jax.random.normal(ks[2], (B,)),
             "s_next": jax.random.normal(ks[3], (B, D)),
             "done": jnp.zeros((B,))}
    oracle = jax.jit(dqn_td_update)
    fused = jax.jit(lambda e, t, o, b: dqn_td_update_fused(
        e, t, o, b, interpret=False))
    iters = 10 if quick else 50
    _, t_o = timer(lambda: jax.block_until_ready(
        oracle(ep, tp, opt, batch)[0].w1), warmup=2, iters=iters)
    _, t_f = timer(lambda: jax.block_until_ready(
        fused(ep, tp, opt, batch)[0].w1), warmup=2, iters=iters)
    return {"skipped": False, "protocol": status(), "parity": parity,
            "td_update_us": {"oracle_xla": round(t_o * 1e6, 2),
                             "fused_kernel": round(t_f * 1e6, 2),
                             "speedup": round(t_o / t_f, 2)}}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def run(quick: bool = True) -> list:
    from benchmarks.common import host_tuning, row, save
    from repro.kernels.protocol import status

    t0 = time.time()
    parity = _interpret_parity(interpret=True)
    trajectory = _td_trajectory(64, interpret=True)
    trainer = _trainer_no_regression(tasks=256 if quick else 384)
    compiled = _compiled_leg(quick)

    gate_ok = bool(parity["all_ok"] and trajectory["ok"] and trainer["ok"]
                   and (compiled.get("skipped")
                        or compiled["parity"]["all_ok"]))
    summary = {
        "protocol": status(),
        "interpret_parity": parity,
        "td_trajectory": trajectory,
        "cpu_trainer": trainer,
        "compiled": compiled,
        "gate": {
            "ok": gate_ok,
            "parity_ok": parity["all_ok"],
            "trajectory_ok": trajectory["ok"],
            "trainer_no_regression_ok": trainer["ok"],
            "compiled_leg": ("skipped: " + compiled["reason"])
            if compiled.get("skipped") else "ran",
        },
        "host_tuning": host_tuning(),
        "wall_s": round(time.time() - t0, 1),
    }
    with open(os.path.join(os.getcwd(), "BENCH_kernels.json"), "w") as f:
        json.dump(summary, f, indent=1)

    rows = [
        row("kernels/interpret_parity_ok", 0.0, parity["all_ok"]),
        row("kernels/td_trajectory_max_param_diff", 0.0,
            f"{trajectory['max_param_diff']:.2e}"),
        row("kernels/default_path_pallas_free", 0.0,
            trainer["default_pallas_free"]),
        row("kernels/td_kernel_on_vs_off_ratio_interpret", 0.0,
            f"{trainer['on_vs_off_ratio']}x"),
        row("kernels/compiled_leg", 0.0,
            "ran" if not compiled.get("skipped") else "skipped"),
        row("kernels/gate_ok", 0.0, gate_ok),
    ]
    save("kernels", rows)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args(argv)
    for r in run(quick=not args.full):
        print(r)
    return 0


if __name__ == "__main__":
    sys.exit(main())
