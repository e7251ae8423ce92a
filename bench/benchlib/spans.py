"""The benchmark's own host spans, and a count of compilations.

A :class:`Timeline` is a list of marks on the host clock; each mark
closes the interval since the one before it and names what the host did
in it.  The harness marks around its own calls into the program, and the
engine's per-segment and per-request hooks mark inside the serving loop.
"""
from __future__ import annotations

import time

# names of what the host did in an interval of the serving loop, by the
# mark that closes it
SERVING = ("admission", "dispatch", "drain", "complete")


class Timeline:
    def __init__(self):
        self.marks: list = []

    def mark(self, label: str, t: float | None = None) -> float:
        t = time.perf_counter() if t is None else t
        self.marks.append((t, label))
        return t

    def intervals(self, lo: float = float("-inf"),
                  hi: float = float("inf")) -> list:
        """``[(label, start, end)]`` clipped to ``[lo, hi]``."""
        out = []
        for (t0, _), (t1, label) in zip(self.marks, self.marks[1:]):
            s, e = max(t0, lo), min(t1, hi)
            if e > s:
                out.append((label, s, e))
        return out

    def seconds(self, labels, lo: float = float("-inf"),
                hi: float = float("inf")) -> float:
        return sum(e - s for label, s, e in self.intervals(lo, hi)
                   if label in labels)


class CompileCounter:
    """Counts JAX traces and backend compilations after :meth:`arm`."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.armed = False
        self.count = 0
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event in self.EVENTS:
            self.count += 1

    def arm(self) -> None:
        self.armed, self.count = True, 0

    def disarm(self) -> int:
        self.armed = False
        return self.count
