"""End-to-end driver (the paper's kind: SERVING): driving environment ->
camera task queue -> FlexAI scheduling -> heterogeneous virtual-accelerator
pools actually executing the perception CNNs with batched requests.

    XLA_FLAGS=--xla_force_host_platform_device_count=3 \
        PYTHONPATH=src python examples/serve_driving_pipeline.py

(each of the three pools owns a device of its own.)

This is the TPU adaptation of Fig 5's data path: cameras -> per-camera
buffers -> RL scheduling strategy -> per-accelerator execution, with the
accelerators realized as device pools running reduced-width YOLO/SSD/GOTURN
and advertising *measured* rates (see repro/core/virtual_platform.py).
"""
import time

import numpy as np

from repro.core.environment import EnvironmentParams, build_task_queue
from repro.core.flexai import FlexAIAgent, FlexAIConfig
from repro.core.schedulers import get_scheduler
from repro.core.virtual_platform import VirtualPlatform

print("calibrating virtual accelerator pools (compiling perception CNNs)...")
t0 = time.time()
plat = VirtualPlatform(run_real=True)
for pool in plat.pools:
    print(f"  pool {pool.spec.name} [{pool.spec.archetype}]: "
          + ", ".join(f"{k}={v:.0f} fps" for k, v in
                      pool.measured_fps.items()))
print(f"calibration took {time.time()-t0:.1f}s")

# scale the camera rates to the measured pool capacity
cap = sum(np.mean(list(p.measured_fps.values())) for p in plat.pools)
rate_scale = min(1.0, cap / 1800.0)
print(f"aggregate capacity ~{cap:.0f} fps -> rate_scale={rate_scale:.4f}")

queue = build_task_queue(EnvironmentParams(route_km=0.02,
                                           rate_scale=rate_scale, seed=0))[:400]
print(f"task queue: {len(queue)} tasks")

# quick FlexAI training on the measured platform (simulated execution),
# then run the real pipeline
sim = VirtualPlatform(run_real=False)
agent = FlexAIAgent(sim, FlexAIConfig(min_replay=64, eps_decay_steps=3000,
                                      update_every=4))
agent.train(sim, [queue], episodes=2)

print("running the real pipeline (frames actually execute on pools)...")
plat.reset()
t0 = time.time()
summary = agent.schedule(plat, queue)
wall = time.time() - t0
print(f"FlexAI:   STM={summary['stm_rate']:.2f} "
      f"R_Balance={summary['r_balance']:.2f} wall={wall:.1f}s")

plat.reset()
summary = get_scheduler("worst").schedule(plat, queue)
print(f"worst:    STM={summary['stm_rate']:.2f} "
      f"R_Balance={summary['r_balance']:.2f}")
