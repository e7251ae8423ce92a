"""Reduction from a profiler trace to device metrics.

A trace is read once into plain data — planes, each with lines, each
with events ``(name, start_ns, duration_ns)`` — so that the reduction
can be tested on a small synthetic trace without a chip.  Device planes
are those named ``/device:<X>`` for an accelerator ``<X>``; on each, the
operations are the events of its ``XLA Ops`` line (all its lines but
the step and module summaries where a backend has no such line).
"""
from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
SUMMARY_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code")


def load_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` into ``{"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith(
        "/device:CPU")


def op_name(text: str) -> str:
    """An HLO op's name without its defining text (``%fusion.86 = ...``
    becomes ``fusion.86``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def device_ops(trace: dict) -> list:
    """``[[(name, start_ns, end_ns), ...] per device plane]``."""
    out = []
    for plane in trace["planes"]:
        if not is_device_plane(plane["name"]):
            continue
        lines = [ln for ln in plane["lines"] if ln["name"] == OPS_LINE]
        if not lines:
            lines = [ln for ln in plane["lines"]
                     if ln["name"] not in SUMMARY_LINES]
        ops = [(op_name(e[0]), e[1], e[1] + e[2]) for ln in lines
               for e in ln["events"]]
        out.append(ops)
    return out


def host_event(trace: dict, name: str):
    """``(start_ns, end_ns)`` of the first host event called ``name``."""
    for plane in trace["planes"]:
        if is_device_plane(plane["name"]):
            continue
        for ln in plane["lines"]:
            for e in ln["events"]:
                if e[0] == name:
                    return e[1], e[1] + e[2]
    return None


def union(intervals, lo: float, hi: float) -> list:
    """Merged ``[start, end]`` intervals clipped to ``[lo, hi]``."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def reduce(trace: dict, lo_ns: float, hi_ns: float,
           host_spans=(), top: int = 10) -> dict:
    """Device metrics of the window ``[lo_ns, hi_ns]``:

    * ``busy_s``: seconds in which some operation ran, averaged over the
      device planes; ``window_s``: the window's length;
    * ``op_s``: summed device seconds per operation name, over all
      devices;
    * ``device_ops``: the ``top`` operations by summed seconds;
    * ``idle_gaps``: idle device seconds apportioned to the host span
      (``(label, start_ns, end_ns)``) that overlaps them, ``"other"``
      where none does, the ``top`` labels by seconds.
    """
    per_dev = device_ops(trace)
    if not per_dev:
        raise ValueError("the trace holds no device plane")
    window_s = (hi_ns - lo_ns) * 1e-9
    busy_total, op_s, idle = 0.0, {}, {}
    spans = sorted(host_spans, key=lambda s: s[1])
    for ops in per_dev:
        busy = union([(s, e) for _, s, e in ops], lo_ns, hi_ns)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        for name, s, e in ops:
            d = min(e, hi_ns) - max(s, lo_ns)
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d * 1e-9
        for gs, ge in gaps(busy, lo_ns, hi_ns):
            covered = 0.0
            for label, ss, se in spans:
                ov = min(ge, se) - max(gs, ss)
                if ov > 0:
                    idle[label] = idle.get(label, 0.0) + ov * 1e-9
                    covered += ov
            if ge - gs - covered > 0:
                idle["other"] = idle.get("other", 0.0) + (
                    ge - gs - covered) * 1e-9
    n = len(per_dev)
    by_time = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_total / n,
        "window_s": window_s,
        "devices": n,
        "op_s": op_s,
        "device_ops": [[k, v] for k, v in by_time[:top]],
        "idle_gaps": [[k, v / n] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])[:top]],
    }


ANCHOR = "bench_clock_anchor"


def capture(trace_dir: str, serve, timeline) -> dict:
    """Run ``serve()`` under the profiler and reduce its trace.

    The host spans of ``timeline`` are moved onto the trace's clock by an
    anchor annotation whose host-clock time is known to within its own
    few microseconds."""
    import time

    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        t_a0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass
        t_a1 = time.perf_counter()
        lo = time.perf_counter()
        serve()
        hi = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    tr = load_xplane(find_xplane(trace_dir))
    anchor = host_event(tr, ANCHOR)
    if anchor is None:
        raise ValueError(f"the trace holds no {ANCHOR} event")
    offset = anchor[0] - 0.5 * (t_a0 + t_a1) * 1e9
    spans = [(label, s * 1e9 + offset, e * 1e9 + offset)
             for label, s, e in timeline.intervals(lo, hi)]
    out = reduce(tr, lo * 1e9 + offset, hi * 1e9 + offset, spans)
    ops = [op for dev in device_ops(tr) for op in dev]
    out["ops_total"] = len(ops)
    out["ops_outside_window"] = sum(
        1 for _, s, e in ops if e < lo * 1e9 + offset or s > hi * 1e9 + offset)
    out["planes"] = {p["name"]: {ln["name"]: len(ln["events"])
                                 for ln in p["lines"]}
                     for p in tr["planes"] if is_device_plane(p["name"])}
    return out
