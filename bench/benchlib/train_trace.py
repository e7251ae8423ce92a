"""The profiled window of a training cell, reduced in one pass.

A traced training window holds millions of device operations (every
step of a 32,768-step scan, each with its fusions), too many to copy
into Python lists as ``benchlib.trace.load_xplane`` does.  :func:`reduce`
streams each line's events once and gives what ``benchlib.trace.reduce``
gives (``busy_s``, ``window_s``, ``devices``, ``device_ops``,
``idle_gaps``), counting as devices only the planes that hold
operations (a TPU trace also has an empty ``/device:CUSTOM:Megascale
Trace`` plane), with, from the same pass, the device seconds of each XLA
module (the ``XLA Modules`` line) and of the operations of one Pallas
kernel, found by the name its ``pallas_call`` gives (XLA names the
custom call ``<name>.<k>``).  :func:`capture` runs a window under the
profiler and reduces its trace.
"""
from __future__ import annotations

import time
from array import array

from benchlib import trace as tracing

MODULES_LINE = "XLA Modules"


def is_kernel(name: str, kernel: str) -> bool:
    """Whether an operation is ``kernel``'s custom call: named ``kernel``
    or ``kernel.<k>``."""
    return name == kernel or (name.startswith(kernel + ".")
                              and name[len(kernel) + 1:].isdigit())


def _ops_lines(lines: list) -> list:
    """A device plane's operation lines, as ``trace.device_ops`` picks
    them."""
    ops = [ln for ln in lines if ln[0] == tracing.OPS_LINE]
    return ops or [ln for ln in lines if ln[0] not in tracing.SUMMARY_LINES]


def reduce(planes, lo_ns: float, hi_ns: float, kernel: str,
           host_spans=(), top: int = 10) -> dict:
    """Device metrics of the window ``[lo_ns, hi_ns]`` of ``planes``: an
    iterable of ``(plane_name, [(line_name, events), ...])`` whose events
    iterate as ``(name, start_ns, duration_ns)``, each line read once.

    Returns ``trace.reduce``'s keys and ``module_s`` (device seconds per
    XLA module), ``kernel_s`` and ``kernel_ops`` (the seconds and the
    operation names of ``kernel``), ``ops_total``,
    ``ops_outside_window`` and ``planes`` (events per line)."""
    window_s = (hi_ns - lo_ns) * 1e-9
    spans = sorted(host_spans, key=lambda s: s[1])
    busy_total, op_s, idle, module_s = 0.0, {}, {}, {}
    counts, n_dev, ops_total, outside = {}, 0, 0, 0
    for pname, lines in planes:
        if not tracing.is_device_plane(pname):
            continue
        lines = list(lines)
        per_line = counts.setdefault(pname, {})
        op_lines = {id(ln) for ln in _ops_lines(lines)}
        starts, ends = array("d"), array("d")
        for ln in lines:
            lname, events = ln
            n = 0
            if id(ln) in op_lines:
                for raw, s, d in events:
                    n += 1
                    e = s + d
                    if e < lo_ns or s > hi_ns:
                        outside += 1
                    starts.append(s)
                    ends.append(e)
                    ov = min(e, hi_ns) - max(s, lo_ns)
                    if ov > 0:
                        name = tracing.op_name(raw)
                        op_s[name] = op_s.get(name, 0.0) + ov * 1e-9
                ops_total += n
            elif lname == MODULES_LINE:
                for raw, s, d in events:
                    n += 1
                    ov = min(s + d, hi_ns) - max(s, lo_ns)
                    if ov > 0:
                        key = raw.split("(", 1)[0]
                        module_s[key] = module_s.get(key, 0.0) + ov * 1e-9
            else:
                for _ in events:
                    n += 1
            per_line[lname] = n
        if not starts:
            continue      # a plane with no operations is no device
        n_dev += 1
        busy = tracing.union(zip(starts, ends), lo_ns, hi_ns)
        del starts, ends
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for gs, ge in tracing.gaps(busy, lo_ns, hi_ns):
            covered = 0.0
            for label, ss, se in spans:
                ov = min(ge, se) - max(gs, ss)
                if ov > 0:
                    idle[label] = idle.get(label, 0.0) + ov * 1e-9
                    covered += ov
            if ge - gs - covered > 0:
                idle["other"] = idle.get("other", 0.0) + (
                    ge - gs - covered) * 1e-9
    if not n_dev:
        raise ValueError("the trace holds no device plane")
    by_time = sorted(op_s.items(), key=lambda kv: -kv[1])
    kernel_ops = sorted(k for k in op_s if is_kernel(k, kernel))
    return {
        "busy_s": busy_total / n_dev,
        "window_s": window_s,
        "devices": n_dev,
        "device_ops": [[k, v] for k, v in by_time[:top]],
        "idle_gaps": [[k, v / n_dev] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
        "module_s": module_s,
        "kernel_s": sum(op_s[k] for k in kernel_ops),
        "kernel_ops": kernel_ops,
        "ops_total": ops_total,
        "ops_outside_window": outside,
        "planes": counts,
    }


def _xplane(path: str):
    """``(plane_name, [(line_name, events)])`` of an ``.xplane.pb``, the
    events read lazily."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)

    def events(line):
        for ev in line.events:
            yield ev.name, ev.start_ns, ev.duration_ns

    for plane in pd.planes:
        yield plane.name, [(ln.name, events(ln)) for ln in plane.lines]


def _anchor(path: str):
    """``(start_ns, end_ns)`` of the anchor annotation on a host plane."""
    for pname, lines in _xplane(path):
        if tracing.is_device_plane(pname):
            continue
        for _, events in lines:
            for name, s, d in events:
                if name == tracing.ANCHOR:
                    return s, s + d
    return None


def capture(trace_dir: str, serve, timeline, kernel: str) -> dict:
    """Run ``serve()`` under the profiler and :func:`reduce` its window,
    whose host marks (``timeline``) label the idle gaps."""
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        t_a0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracing.ANCHOR):
            pass
        t_a1 = time.perf_counter()
        lo = time.perf_counter()
        serve()
        hi = time.perf_counter()
    finally:
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        t_read = time.perf_counter()
    path = tracing.find_xplane(trace_dir)
    anchor = _anchor(path)
    if anchor is None:
        raise ValueError(f"the trace holds no {tracing.ANCHOR} event")
    offset = anchor[0] - 0.5 * (t_a0 + t_a1) * 1e9
    spans = [(label, s * 1e9 + offset, e * 1e9 + offset)
             for label, s, e in timeline.intervals(lo, hi)]
    out = reduce(_xplane(path), lo * 1e9 + offset, hi * 1e9 + offset,
                 kernel, spans)
    out["stop_s"] = t_read - t_stop
    out["read_s"] = time.perf_counter() - t_read
    return out
