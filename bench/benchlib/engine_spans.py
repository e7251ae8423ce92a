"""The serving engine's own spans and counters, read for the benchmark.

A ``repro.serve.tracing.Tracer`` attached to the engine for a profiled
window gives two views of the same spans:

* in memory, :func:`window` reduces the tracer to the plain data a
  metric reader takes from its run's ``ctx["spans"]``: the tracer's
  ``summary`` over the window and the ``queued`` durations (ms) of the
  requests admitted in it;
* in the profiler's trace, each span is a host annotation of the same
  name on the device trace's clock: :func:`host_events` finds them and
  :func:`idle_by_span` gives each idle device instant to the innermost
  engine span open at that instant (``other`` where none is).

The readers return None where the run carries no spans, so a run made
without a tracer leaves their metrics out of its line.
"""
from __future__ import annotations

from benchlib import trace as tracing
from benchlib.result import percentile

# the engine's span names (repro.serve.qos, repro.serve.durability)
LOOP = ("admit", "segment", "drain")
NAMES = LOOP + ("admit.pack_tasks", "admit.init_state", "segment.slice",
                "segment.call", "drain.records", "drain.state",
                "drain.summarize", "hook", "snapshot", "gc")


def window(tracer, lo_ns: int, hi_ns: int) -> dict:
    """``ctx["spans"]`` of a window ``[lo_ns, hi_ns]`` on the host clock
    (``time.perf_counter_ns``)."""
    out = tracer.summary(lo_ns, hi_ns)
    out["queued_ms"] = tracer.durations_ms("queued", lo_ns, hi_ns)
    return out


def host_events(trace: dict, names) -> list:
    """``[(name, start_ns, end_ns)]`` of the host events called one of
    ``names``, in start order."""
    want = set(names)
    out = [(e[0], e[1], e[1] + e[2])
           for plane in trace["planes"]
           if not tracing.is_device_plane(plane["name"])
           for ln in plane["lines"] for e in ln["events"] if e[0] in want]
    return sorted(out, key=lambda ev: (ev[1], -ev[2]))


def innermost(spans, lo: float, hi: float) -> list:
    """Partition ``[lo, hi]`` into ``[(label, start, end)]``, each piece
    labelled by the innermost span open in it (the one opened last), or
    ``"other"``.  Spans nest; one that starts inside another and ends
    after it counts as innermost until its own end."""
    out: list = []
    stack: list = []
    t = lo

    def emit(upto):
        nonlocal t
        upto = min(max(upto, lo), hi)
        if upto > t:
            out.append((stack[-1][0] if stack else "other", t, upto))
            t = upto

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= s:
            emit(stack[-1][2])
            stack.pop()
        emit(s)
        stack.append((name, s, e))
    while stack:
        emit(stack[-1][2])
        stack.pop()
    emit(hi)
    return out


def idle_by_span(trace: dict, lo_ns: float, hi_ns: float,
                 names=NAMES, top: int = 10) -> list:
    """Idle device seconds of ``[lo_ns, hi_ns]`` by the innermost engine
    span open over them, averaged over the device planes: ``[[label,
    seconds]]``, the ``top`` labels by seconds.  Every idle instant is
    counted once, so the values sum to the idle time."""
    pieces = innermost(host_events(trace, names), lo_ns, hi_ns)
    per_dev = tracing.device_ops(trace)
    idle: dict = {}
    for ops in per_dev:
        busy = tracing.union([(s, e) for _, s, e in ops], lo_ns, hi_ns)
        gaps = tracing.gaps(busy, lo_ns, hi_ns)
        i = 0
        for label, ps, pe in pieces:
            while i < len(gaps) and gaps[i][1] <= ps:
                i += 1
            j = i
            while j < len(gaps) and gaps[j][0] < pe:
                ov = min(pe, gaps[j][1]) - max(ps, gaps[j][0])
                if ov > 0:
                    idle[label] = idle.get(label, 0.0) + ov * 1e-9
                j += 1
    n = max(len(per_dev), 1)
    return [[k, v / n] for k, v in sorted(idle.items(),
                                          key=lambda kv: -kv[1])[:top]]


def module_seconds(trace: dict, lo_ns: float, hi_ns: float) -> dict:
    """Device seconds per XLA module (the ``XLA Modules`` line) inside
    the window, summed over the device planes."""
    out: dict = {}
    for plane in trace["planes"]:
        if not tracing.is_device_plane(plane["name"]):
            continue
        for ln in plane["lines"]:
            if ln["name"] != "XLA Modules":
                continue
            for name, s, d in ln["events"]:
                ov = min(s + d, hi_ns) - max(s, lo_ns)
                if ov > 0:
                    key = name.split("(", 1)[0]
                    out[key] = out.get(key, 0.0) + ov * 1e-9
    return out


# -- readers ------------------------------------------------------------

def _loop_ns(ctx: dict, name: str):
    """``name``'s total less its ``hook`` children, or None."""
    e = (ctx.get("spans") or {}).get("spans", {}).get(name)
    if e is None:
        return None
    return e["total_ns"] - e["children"].get("hook", 0)


def _dispatches(ctx: dict):
    tr = ctx.get("trace") or {}
    return tr.get("dispatches") or None


def admit_ms_per_wave(ctx: dict):
    ns = _loop_ns(ctx, "admit")
    waves = ((ctx.get("spans") or {}).get("counters", {})
             .get("waves_admitted"))
    return ns * 1e-6 / waves if ns is not None and waves else None


def ms_per_segment(ctx: dict, name: str):
    ns, d = _loop_ns(ctx, name), _dispatches(ctx)
    return ns * 1e-6 / d if ns is not None and d else None


def counter_per_segment(ctx: dict, name: str):
    spans, d = ctx.get("spans"), _dispatches(ctx)
    if not spans or not d or name not in spans["counters"]:
        return None
    return spans["counters"][name] / d


def queue_ms_p50(ctx: dict):
    q = (ctx.get("spans") or {}).get("queued_ms")
    return percentile(q, 50) if q else None
