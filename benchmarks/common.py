"""Shared benchmark plumbing.

Every benchmark module exposes ``run(quick: bool) -> list[dict]`` where each
dict has at least {"name", "us_per_call", "derived"}; ``benchmarks/run.py``
prints them as CSV (one row per measured quantity) and writes the full JSON
to experiments/bench/.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

RESULTS_DIR = os.environ.get("BENCH_RESULTS_DIR", "experiments/bench")

# load-matched subsampling (see HMAIPlatform.capacity_scale)
RATE_SCALE = 0.05

# ---------------------------------------------------------------------------
# XLA host tuning (recorded in every BENCH_*.json)
# ---------------------------------------------------------------------------

# Keeps the per-step host marker out of the compiled region, so scan-heavy
# dispatches are not split at arbitrary points by profiling markers.
STEP_MARKER_FLAG = "--xla_step_marker_location=STEP_MARK_AT_ENTRY"

_TCMALLOC_GLOBS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc*.so*",
    "/usr/lib/libtcmalloc*.so*",
    "/usr/local/lib/libtcmalloc*.so*",
)


def find_tcmalloc():
    """First tcmalloc shared object on this host, or None.  Preloading it
    cuts allocator contention on many-core hosts; it can only take effect
    via LD_PRELOAD *before* process start, so callers record availability
    here and scripts/ci.sh / spawned children do the actual preload."""
    import glob
    for pat in _TCMALLOC_GLOBS:
        hits = sorted(glob.glob(pat))
        if hits:
            return hits[0]
    return None


def host_tuning(devices: int | None = None) -> dict:
    """The XLA host-tuning flags in effect for this process, as recorded
    in each ``BENCH_*.json`` — so a result file says which knobs were on
    when its numbers were measured (forced host device count, step-marker
    placement, tcmalloc preload)."""
    import re
    flags = os.environ.get("XLA_FLAGS", "")
    forced = re.findall(r"--xla_force_host_platform_device_count=(\d+)",
                        flags)
    tc = find_tcmalloc()
    return {
        "nproc": os.cpu_count(),
        "xla_force_host_platform_device_count":
            int(forced[-1]) if forced
            else (devices if devices is not None else 1),
        "step_marker_at_entry": STEP_MARKER_FLAG in flags,
        "tcmalloc_path": tc,
        "tcmalloc_active": bool(tc)
            and "tcmalloc" in os.environ.get("LD_PRELOAD", ""),
    }


def tuned_child_env(devices: int) -> dict:
    """Environment for a multi-device benchmark child: forced host device
    count (must precede jax import — last flag wins inside XLA_FLAGS),
    step markers at entry, and tcmalloc preloaded when the host has it."""
    env = dict(os.environ)
    base = env.get("XLA_FLAGS", "")
    if STEP_MARKER_FLAG not in base:
        base = f"{base} {STEP_MARKER_FLAG}".strip()
    env["XLA_FLAGS"] = (f"{base} "
                        f"--xla_force_host_platform_device_count={devices}")
    tc = find_tcmalloc()
    if tc and "tcmalloc" not in env.get("LD_PRELOAD", ""):
        env["LD_PRELOAD"] = tc + (os.pathsep + env["LD_PRELOAD"]
                                  if env.get("LD_PRELOAD") else "")
    return env


def timer(fn, *args, warmup: int = 1, iters: int = 3, **kwargs):
    """Returns (last_result, seconds_per_call)."""
    for _ in range(warmup):
        result = fn(*args, **kwargs)
    t0 = time.perf_counter()
    for _ in range(iters):
        result = fn(*args, **kwargs)
    return result, (time.perf_counter() - t0) / iters


def row(name: str, us_per_call: float, derived, **extra) -> dict:
    r = {"name": name, "us_per_call": round(float(us_per_call), 3),
         "derived": derived}
    r.update(extra)
    return r


def save(module: str, rows: list) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, f"{module}.json"), "w") as f:
        json.dump(rows, f, indent=1, default=str)


def spawn_forced_device_child(module: str, devices: int, args: list,
                              result_tag: str, timeout: int = 1200) -> dict:
    """Run ``python -m benchmarks.<module> --child ...`` in a subprocess
    with ``--xla_force_host_platform_device_count`` (which must be set
    before jax imports) and parse the tagged JSON result line — the
    shared protocol of the multi-device benchmark children.

    CPU backend only: forced host devices exist only there, and on a chip
    the parent process already holds the accelerator, so a child that
    needs it would fail or hang.  There :func:`run_device_arm` runs the
    arm in this process instead."""
    import subprocess
    import sys

    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"forced-device children run on the CPU backend only, not "
            f"{backend!r}: run the multi-device arm in one process over "
            f"jax.devices()")
    env = tuned_child_env(devices)
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env["PYTHONPATH"] = os.path.join(root, "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", f"benchmarks.{module}", "--child",
           "--devices", str(devices)] + [str(a) for a in args]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=timeout, cwd=root)
    if out.returncode != 0:
        raise RuntimeError(f"{module} child (devices={devices}) failed:\n"
                           + out.stderr[-2000:])
    return _tagged_result(out.stdout, result_tag)


def _tagged_result(stdout: str, result_tag: str) -> dict:
    line = [l for l in stdout.splitlines() if l.startswith(result_tag)][0]
    return json.loads(line[len(result_tag):])


def arm_devices(devices: int) -> int:
    """Device count of a multi-device arm, checked against what JAX has:
    all of them in a forced-device child, the first ``devices`` of the
    chips when the arm runs in-process (``jax.make_mesh`` takes a prefix)."""
    import jax
    have = len(jax.devices())
    if have < devices:
        raise RuntimeError(f"arm needs {devices} devices, JAX has {have}")
    return devices


def run_device_arm(module: str, devices: int, args: list,
                   result_tag: str) -> dict:
    """Run one multi-device arm of ``benchmarks.<module>`` on ``devices``
    devices and return its tagged JSON result.

    On the CPU backend the arm is a forced-host-device child
    (:func:`spawn_forced_device_child`).  Anywhere else this process holds
    the chips, so the same ``--child`` body runs here, over the first
    ``devices`` of ``jax.devices()``."""
    import contextlib
    import importlib
    import io

    import jax
    if jax.default_backend() == "cpu":
        return spawn_forced_device_child(module, devices, args, result_tag)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        importlib.import_module(f"benchmarks.{module}").main(
            ["--child", "--devices", str(devices)] + [str(a) for a in args])
    return _tagged_result(buf.getvalue(), result_tag)


def queues_for(area: str, n: int, km: float, seed0: int = 0):
    from repro.core.environment import Area, EnvironmentParams, build_task_queue
    return [build_task_queue(EnvironmentParams(
        area=Area(area), route_km=km, rate_scale=RATE_SCALE, seed=seed0 + s))
        for s in range(n)]


def platform():
    from repro.core.hmai import HMAIPlatform
    return HMAIPlatform(capacity_scale=RATE_SCALE)


_AGENT_CACHE = {}


def flexai_ckpt_path(area: str, quick: bool = False) -> str:
    """Per-area checkpoint; quick-mode checkpoints carry a ``_quick``
    suffix so a short smoke train can never masquerade as the full
    "well-trained agent" in a later quick=False run."""
    suffix = "_quick" if quick else ""
    return os.path.join("experiments", "flexai",
                        f"agent_{area.lower()}{suffix}.npz")


def trained_flexai(area: str = "UB", episodes: int = 25, quick: bool = True):
    """Train (or load) a FlexAI agent for an area; cached per process.

    If a usable pre-trained checkpoint for *this area* exists (written by
    a previous benchmark process or the ``launch.train --flexai`` offline
    run), load it — the paper's "well-trained agent".  Full runs only
    accept the full checkpoint; quick runs prefer it but fall back to the
    quick one.  Otherwise train device-resident (``ScanFlexAI`` fused
    episodes with eval-based model selection), export the weights to the
    Python-loop wrapper the figure modules consume, and write the
    checkpoint (plus a loss-history sidecar, so fig11 still has a curve
    when a later process loads the checkpoint instead of retraining).
    """
    key = (area, quick)
    if key in _AGENT_CACHE:
        return _AGENT_CACHE[key]
    from repro.core.flexai import FlexAIAgent, FlexAIConfig, ScanFlexAI
    plat = platform()
    cfg = FlexAIConfig(
        lr=1e-3, gamma=0.98, min_replay=256, update_every=2,
        eps_decay_steps=40000, target_sync_every=500)
    candidates = [flexai_ckpt_path(area)]
    if quick:
        candidates.append(flexai_ckpt_path(area, quick=True))
    ckpt = next((c for c in candidates if os.path.exists(c)), None)
    if ckpt is not None:
        losses_path = ckpt[: -len(".npz")] + "_losses.npy"
        agent = FlexAIAgent(plat, cfg)
        agent.load_weights(ckpt)
        if os.path.exists(losses_path):
            agent.losses = np.load(losses_path).tolist()
    else:
        ckpt = flexai_ckpt_path(area, quick=quick)
        losses_path = ckpt[: -len(".npz")] + "_losses.npy"
        queues = queues_for(area, 4, km=0.15)
        val_q = queues_for(area, 1, km=0.15, seed0=50)[0]
        trainer = ScanFlexAI(plat, cfg)
        trainer.train(queues, episodes=episodes if not quick else 12,
                      eval_queue=val_q, eval_every=4)
        agent = trainer.to_agent(plat)
        os.makedirs(os.path.dirname(ckpt), exist_ok=True)
        agent.save_weights(ckpt)
        np.save(losses_path, np.asarray(trainer.losses, np.float64))
    _AGENT_CACHE[key] = agent
    return agent
