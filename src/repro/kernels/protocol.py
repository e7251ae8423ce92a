"""How the Pallas kernel layer executes, for tests and benchmarks.

* **interpret** — on the CPU backend, which has no Pallas compiler: the
  kernel body runs as ordinary XLA ops.  This validates the math against
  the oracles (what the test suite runs on the CPU) and says nothing
  about Mosaic lowering, VMEM budgets or speed.
* **compiled** — on every other backend: the kernels lower through
  Mosaic and run on the chip.  Only these timings mean anything.

``repro.compat.pallas_interpret_default`` makes the choice; this module
reports it.
"""
from __future__ import annotations

import jax

from repro.compat import pallas_interpret_default


def compiled_available() -> bool:
    """True when the kernels run compiled on this backend."""
    return not pallas_interpret_default()


def status() -> dict:
    """Execution stamp for BENCH_kernels.json and skip messages."""
    return {"backend": jax.devices()[0].platform,
            "mode": "compiled" if compiled_available() else "interpret"}
