"""Production mesh construction.

``make_production_mesh`` is a function (not module-level state) so importing
this module never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benchmarks see the real (1-device) platform.

Mesh construction goes through ``repro.compat.make_mesh``, so every axis
is ``AxisType.Auto``.
"""
from __future__ import annotations

import jax

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_platform_mesh(n_stages: int = 1, devices: int | None = None):
    """Mesh for the device-resident platform engines: 1-D ``("routes",)``
    for pure data parallelism over route lanes, 2-D ``("stages",
    "routes")`` when pipeline stages are placed on accelerator groups
    (``core/pipeline.py``).  The stage axis size must equal the
    ``StagePlan``'s stage count; the route axis takes the remaining
    devices.
    """
    n_dev = devices if devices is not None else len(jax.devices())
    if n_stages <= 1:
        return make_mesh((n_dev,), ("routes",))
    if n_dev % n_stages:
        raise RuntimeError(
            f"{n_dev} device(s) not divisible into {n_stages} stage "
            f"groups; force a device count with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=<k*{n_stages}>")
    return make_mesh((n_stages, n_dev // n_stages), ("stages", "routes"))


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices exist (tests)."""
    import numpy as np
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"need {n} devices; run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return make_mesh(shape, axes)
