"""Arithmetic shared by the per-metric readers in ``bench/metrics``.

Each reader returns None where its run has nothing to read, and the
harness then leaves the metric out of the line."""
from __future__ import annotations

from benchlib.flops import qnet_forward_flops
from benchlib.result import percentile


def latency_percentile(ctx: dict, q: float):
    lat = ctx.get("latencies_ms")
    return percentile(lat, q) if lat else None


def per_second(ctx: dict, count: str):
    n, w = ctx.get(count), ctx.get("window_s")
    return n / w if n and w else None


def host_ms_per_segment(ctx: dict):
    d = ctx.get("dispatches")
    return 1e3 * ctx["serve_s"] / d if d else None


def pad_share(ctx: dict):
    """Per cent of the lane slots dispatched in the window that held
    padding and not a real task."""
    slots = ctx.get("dispatches", 0) * ctx["slots"] * ctx["chunk"]
    return 100.0 * (1.0 - ctx["decisions"] / slots) if slots else None


def idle_share(ctx: dict):
    """Per cent of the traced window in which no operation ran on the
    device, averaged over the chips."""
    tr = ctx.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def serve_mfu(ctx: dict):
    """Q-net forward FLOPs of the real decisions per second, over the
    chips' peak (per cent).  Float32 matmuls at "highest" precision are
    measured against the bfloat16 peak."""
    rate = per_second(ctx, "decisions")
    if not rate:
        return None
    q = ctx["config"]["qnet"]
    flops = qnet_forward_flops(q["state_dim"], q["hidden"], q["n_actions"])
    return 100.0 * flops * rate / (ctx["peak"]["bf16_flops"] * ctx["chips"])


def device_ms_per_segment(ctx: dict):
    """Device busy time of the traced window per segment dispatched in
    it."""
    tr = ctx.get("trace")
    if not tr or not tr.get("dispatches"):
        return None
    return 1e3 * tr["busy_s"] / tr["dispatches"]
