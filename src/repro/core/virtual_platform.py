"""TPU adaptation of HMAI: heterogeneous *virtual accelerators* as
sub-mesh pools (DESIGN.md §3, platform level).

HMAI's accelerator-level parallelism maps onto a TPU pod by partitioning
the device mesh into pools, each compiled for one perception-workload class
with the dataflow archetype that suits it (the paper's SconvOD / SconvIC /
MconvMC affinities).  The FlexAI scheduler drives the pools through the
same queue interface as the simulated HMAI: each pool advertises a
*measured* FPS per model class (calibrated at startup by timing a warm
batch), and ``execute`` really runs the batch.

On this CPU container the pools are host-device groups and the models are
the reduced-width perception CNNs — the structure (mesh partitioning,
per-pool compilation, measured-rate scheduling) is exactly what deploys on
a real pod.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hmai as H
from repro.core.tasks import TaskKind


@dataclasses.dataclass
class PoolSpec:
    name: str
    archetype: str          # taxonomy archetype this pool emulates
    n_devices: int
    batch_size: int = 4
    width_mult: float = 0.1  # reduced CNNs for CPU-scale runs


class _ModelBank:
    """Shared, compiled-once perception nets (params passed as args so one
    jit compilation serves every pool)."""

    _instance = None

    def __init__(self, key, width_mult: float, batch_size: int):
        from repro.models.perception.cnn import convnet_apply
        from repro.models.perception.nets import (
            GOTURN_TOWER, SSD_SPEC, YOLO_SPEC, goturn_apply, init_convnet,
            init_goturn)
        from repro.sharding import unbox
        k1, k2, k3 = jax.random.split(key, 3)
        goturn_p = unbox(init_goturn(k3, max(0.2, width_mult)))
        head_spec = goturn_p.pop("head_spec")  # static: closed over, not traced
        self.params = {
            "yolo": unbox(init_convnet(k1, YOLO_SPEC, width_mult)),
            "ssd": unbox(init_convnet(k2, SSD_SPEC, width_mult)),
            "goturn": goturn_p,
        }
        self.fns = {
            "yolo": jax.jit(lambda p, x: convnet_apply(p, YOLO_SPEC, x)),
            "ssd": jax.jit(lambda p, x: convnet_apply(p, SSD_SPEC, x)),
            "goturn": jax.jit(lambda p, x: goturn_apply(
                {**p, "head_spec": head_spec}, x, x)),
        }
        self.inputs = {
            "yolo": jnp.zeros((batch_size, 64, 64, 3)),
            "ssd": jnp.zeros((batch_size, 64, 64, 3)),
            "goturn": jnp.zeros((batch_size, 32, 32, 3)),
        }

    @classmethod
    def get(cls, key, width_mult, batch_size):
        if cls._instance is None:
            cls._instance = cls(key, width_mult, batch_size)
        return cls._instance


class VirtualAcceleratorPool:
    """A device group serving the shared model bank (per-pool params would
    differ in deployment; the pool's identity here is its device count and
    dataflow archetype)."""

    def __init__(self, spec: PoolSpec, devices, key):
        self.spec = spec
        self.devices = devices
        self.bank = _ModelBank.get(key, spec.width_mult, spec.batch_size)
        self.inputs = self.bank.inputs
        self.measured_fps: dict = {}

    def calibrate(self) -> dict:
        """Measure frames/s per model class (warm, batched)."""
        for kind, fn in self.bank.fns.items():
            x = self.inputs[kind]
            p = self.bank.params[kind]
            jax.block_until_ready(fn(p, x))  # compile + warm
            t0 = time.perf_counter()
            iters = 3
            for _ in range(iters):
                jax.block_until_ready(fn(p, x))
            dt = (time.perf_counter() - t0) / iters
            # a pool of n devices serves n batches concurrently
            self.measured_fps[kind] = (x.shape[0] * self.spec.n_devices) / dt
        return self.measured_fps

    def run(self, kind: str, frames: jax.Array):
        return self.bank.fns[kind](self.bank.params[kind], frames)

    def as_accelerator_spec(self) -> H.AcceleratorSpec:
        from repro.core.taxonomy import TAXONOMY
        return H.AcceleratorSpec(
            name=f"pool:{self.spec.name}",
            arch=TAXONOMY[self.spec.archetype],
            fps=dict(self.measured_fps),
            power_w=H.ACCELERATOR_SPECS[self.spec.archetype].power_w
            * self.spec.n_devices)


DEFAULT_POOLS = (
    PoolSpec("det-large", "MconvMC", n_devices=1),
    PoolSpec("det-small", "SconvOD", n_devices=1),
    PoolSpec("tracking", "SconvIC", n_devices=1),
)


class VirtualPlatform(H.HMAIPlatform):
    """HMAIPlatform whose specs come from measured pool rates and whose
    ``execute`` really runs the batch on the pool."""

    def __init__(self, pool_specs=DEFAULT_POOLS, seed: int = 0,
                 run_real: bool = True):
        devices = jax.devices()
        wanted = sum(ps.n_devices for ps in pool_specs)
        if wanted > len(devices):
            # pools own disjoint device groups; stacking them on one
            # device would advertise capacity that is not there
            raise ValueError(
                f"pools ask for {wanted} devices, {len(devices)} available "
                f"(on the CPU backend, force more with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={wanted})")
        self.pools: list[VirtualAcceleratorPool] = []
        key = jax.random.PRNGKey(seed)
        di = 0
        for i, ps in enumerate(pool_specs):
            devs = devices[di: di + ps.n_devices]
            di += ps.n_devices
            pool = VirtualAcceleratorPool(ps, devs, jax.random.fold_in(key, i))
            pool.calibrate()
            self.pools.append(pool)
        specs = [p.as_accelerator_spec() for p in self.pools]
        super().__init__(specs=specs)
        self.run_real = run_real

    def execute(self, task, accel_index: int):
        if self.run_real:
            pool = self.pools[accel_index]
            frames = pool.inputs[task.kind.value]
            jax.block_until_ready(pool.run(task.kind.value, frames))
        return super().execute(task, accel_index)
