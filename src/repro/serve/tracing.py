"""Spans and transfer counters of the QoS serving loop and of the fused
FlexAI trainer.

A :class:`Tracer` attached to a ``QoSPlacementEngine`` or a
``core.flexai.ScanFlexAI`` trainer (``engine.tracer = Tracer()``;
``None`` detaches it) records, in memory:

* spans ``(name, start_ns, end_ns, parent, wave, uid)`` on
  ``time.perf_counter_ns``.  ``parent`` is the index of the enclosing
  span in :attr:`Tracer.spans`; ``wave`` is the index into the engine's
  ``wave_log`` of the admission round the span belongs to, inherited
  from the parent when not given; ``uid`` names a request.  Each span
  also opens a ``jax.profiler.TraceAnnotation`` of the same name, so
  under the profiler the spans land in the trace's host plane on the
  device trace's own clock.  ``queued`` spans (one per request, submit
  to admission) are recorded after the fact from the request's
  timestamps and open no annotation: they overlap the loop's spans
  rather than nest in them;
* counters, as totals and as a time-stamped log, so that
  :meth:`Tracer.summary` can report their deltas over an interval.

The counting rule: ``d2h_transfers`` / ``d2h_bytes`` count the
``jax.Array`` leaves the engine brings to the host, one transfer per
leaf: a call's records where their copy starts, at dispatch
(``d2h_early`` counts these early starts alone), and the wave's state
once per drain, when lanes complete; ``h2d_transfers`` /
``h2d_bytes`` count the ``np.ndarray`` leaves handed to a jitted
segment call (params, task slice, state and, for pipeline waves, the
stage slice and ring), each uploaded once by the call;
``waves_admitted`` counts admission rounds, one per ``wave_log`` entry;
``segment_calls`` counts jitted segment calls, each of which serves one
or more ``chunk``-task segments (the engine's ``dispatches`` counts
those), so Δ``dispatches`` ÷ Δ``segment_calls`` is the segments a call
serves (half a FIFO drain wave's: ``_segments_per_call`` in
``serve/qos.py``);
``fresh_state_reuses`` counts the fresh waves that took the engine's
constant initial checkpoint (a resumed wave keeps its own, and a
continuous refill resets one lane), so in drain mode without
preemptions it equals ``waves_admitted``.  Lanes that reach their end
at one segment boundary drain together: in drain mode a wave drains
once, so ``d2h_transfers`` is 10 record leaves a call plus 11 state
leaves a wave.
In the trainer the same rule counts an episode's task arrays uploaded
(``episode.upload``) and its records, losses and update mask brought to
the host (``episode.fetch``); ``episodes`` counts fused episodes,
``train_steps`` the valid tasks they trained on and ``td_updates`` the
TD updates they made (the update mask's sum).

While a tracer is attached to an engine, every garbage collection of
the process is recorded as a ``gc`` span, whose parent is the span open
when it started.

While none is attached an engine holds :data:`DETACHED`, whose calls
do nothing: no clock read, no annotation object, no allocation.
"""
from __future__ import annotations

import contextlib
import gc
import time

import jax
import numpy as np

__all__ = ["Tracer", "Span", "DETACHED"]

# the context every span site enters when no tracer is attached
OFF = contextlib.nullcontext()


class _Detached:
    """The tracer an engine holds while none is attached: every span is
    the shared ``OFF`` context, every count a no-op."""
    __slots__ = ()

    def span(self, name: str, wave=None, uid=None):
        return OFF

    def record(self, name: str, start_ns: int, end_ns: int, wave=None,
               uid=None) -> None:
        pass

    def count(self, name: str, n: int = 1) -> None:
        pass

    def to_host(self, tree) -> None:
        pass

    def to_device(self, *args) -> None:
        pass

    def attach(self) -> None:
        pass

    def detach(self) -> None:
        pass


DETACHED = _Detached()


class Span:
    """One interval of a traced loop, and its own context manager."""
    __slots__ = ("name", "start_ns", "end_ns", "parent", "wave", "uid",
                 "_tracer", "_ann")

    def __init__(self, tracer, name, wave=None, uid=None):
        self._tracer = tracer
        self.name = name
        self.wave = wave
        self.uid = uid
        self.start_ns = self.end_ns = None
        self.parent = None

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._tracer._open(self)
        return self

    def __exit__(self, *exc):
        self._tracer._close(self)
        self._ann.__exit__(*exc)
        self._ann = None
        return False


class Tracer:
    def __init__(self):
        self.spans: list = []
        self.counters: dict = {}
        self._log: list = []      # (t_ns, counter, amount)
        self._stack: list = []    # indices of the open spans
        self._gc = None           # the collection in progress

    # -- spans ----------------------------------------------------------
    def span(self, name: str, wave=None, uid=None) -> Span:
        return Span(self, name, wave, uid)

    def _open(self, sp: Span) -> None:
        if self._stack:
            sp.parent = self._stack[-1]
            if sp.wave is None:
                sp.wave = self.spans[sp.parent].wave
        sp.start_ns = time.perf_counter_ns()
        self._stack.append(len(self.spans))
        self.spans.append(sp)

    def _close(self, sp: Span) -> None:
        sp.end_ns = time.perf_counter_ns()
        self._stack.pop()

    def record(self, name: str, start_ns: int, end_ns: int, wave=None,
               uid=None) -> None:
        """An interval already over, outside the nesting (``queued``)."""
        sp = Span(self, name, wave, uid)
        sp.start_ns, sp.end_ns = start_ns, end_ns
        self.spans.append(sp)

    # -- counters -------------------------------------------------------
    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n
        self._log.append((time.perf_counter_ns(), name, n))

    def to_host(self, tree) -> None:
        """Count the device leaves of ``tree`` the caller brings over."""
        self._leaves("d2h", jax.Array, tree)

    def to_device(self, *args) -> None:
        """Count the host leaves of a jitted call's arguments."""
        self._leaves("h2d", np.ndarray, args)

    def _leaves(self, way: str, kind: type, tree) -> None:
        n = b = 0
        for x in jax.tree_util.tree_leaves(tree):
            if isinstance(x, kind):
                n += 1
                b += x.nbytes
        self.count(f"{way}_transfers", n)
        self.count(f"{way}_bytes", b)

    # -- garbage collection ----------------------------------------------
    def attach(self) -> None:
        """Called by an engine taking this tracer: records collections."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def detach(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        # a collection may run on another thread (a checkpoint writer):
        # it reads the open span as its parent but never joins the stack
        if phase == "start":
            sp = Span(self, "gc")
            if self._stack:
                sp.parent = self._stack[-1]
                sp.wave = self.spans[sp.parent].wave
            sp._ann = jax.profiler.TraceAnnotation("gc")
            sp._ann.__enter__()
            sp.start_ns = time.perf_counter_ns()
            self._gc = sp
        elif self._gc is not None:
            sp, self._gc = self._gc, None
            sp.end_ns = time.perf_counter_ns()
            sp._ann.__exit__(None, None, None)
            sp._ann = None
            self.spans.append(sp)

    # -- reading ----------------------------------------------------------
    def summary(self, lo_ns: int | None = None,
                hi_ns: int | None = None) -> dict:
        """Per span name closed inside ``[lo_ns, hi_ns]``: ``count``,
        ``total_ns``, ``self_ns`` (total less its direct children) and
        ``children`` (direct children's ns by name); per counter, its
        delta over the interval."""
        lo = -1 if lo_ns is None else lo_ns
        hi = float("inf") if hi_ns is None else hi_ns
        inside = {i for i, sp in enumerate(self.spans)
                  if sp.end_ns is not None and lo <= sp.start_ns
                  and sp.end_ns <= hi}
        spans: dict = {}
        for i in sorted(inside):
            sp = self.spans[i]
            d = sp.end_ns - sp.start_ns
            e = spans.setdefault(sp.name, {"count": 0, "total_ns": 0,
                                           "self_ns": 0, "children": {}})
            e["count"] += 1
            e["total_ns"] += d
            e["self_ns"] += d
        for i in inside:
            sp = self.spans[i]
            if sp.parent in inside:
                d = sp.end_ns - sp.start_ns
                p = spans[self.spans[sp.parent].name]
                p["self_ns"] -= d
                p["children"][sp.name] = p["children"].get(sp.name, 0) + d
        counters: dict = {}
        for t, name, n in self._log:
            if lo <= t <= hi:
                counters[name] = counters.get(name, 0) + n
        return {"spans": spans, "counters": counters}

    def durations_ms(self, name: str, lo_ns: int | None = None,
                     hi_ns: int | None = None) -> list:
        """Lengths of the spans called ``name`` that end inside the
        interval, in ms."""
        lo = -1 if lo_ns is None else lo_ns
        hi = float("inf") if hi_ns is None else hi_ns
        return [(sp.end_ns - sp.start_ns) * 1e-6 for sp in self.spans
                if sp.name == name and sp.end_ns is not None
                and lo <= sp.end_ns <= hi]

    def line(self) -> str:
        """One line: per span, count, total and self ms; the counters."""
        s = self.summary()
        parts = [f"{k} n={v['count']} total_ms={v['total_ns'] * 1e-6:.3f} "
                 f"self_ms={v['self_ns'] * 1e-6:.3f}"
                 for k, v in sorted(s["spans"].items())]
        parts += [f"{k}={v}" for k, v in sorted(s["counters"].items())]
        return "spans: " + "; ".join(parts)
