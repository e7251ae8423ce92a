"""Arithmetic of the training cell's per-layer metric readers.

The spans and counters are those a ``repro.serve.tracing.Tracer``
attached to the trainer recorded over the profiled window
(``ctx["spans"]``); the device seconds are the profiled window's
(``ctx["trace"]``: ``module_s`` per XLA module, ``kernel_s`` of the TD
kernel).  Each reader returns None where its run has nothing to read.
"""
from __future__ import annotations

from benchlib.flops import qnet_forward_flops, roofline_share, \
    td_update_cost

TRAIN_MODULE = "jit_train_episode"


def _counter(ctx: dict, name: str):
    return ((ctx.get("spans") or {}).get("counters") or {}).get(name)


def _td_cost(ctx: dict) -> dict:
    q = ctx["config"]["qnet"]
    return td_update_cost(ctx["config"]["trainer"]["batch_size"],
                          q["state_dim"], q["hidden"], q["n_actions"])


def host_ms_per_episode(ctx: dict):
    """Host ms per episode outside the jitted call: ``episode`` less
    ``episode.call``, over the episodes of the profiled window."""
    spans = (ctx.get("spans") or {}).get("spans", {})
    n = _counter(ctx, "episodes")
    if "episode" not in spans or "episode.call" not in spans or not n:
        return None
    ns = spans["episode"]["total_ns"] - spans["episode.call"]["total_ns"]
    return ns * 1e-6 / n


def _module_s(ctx: dict):
    return ((ctx.get("trace") or {}).get("module_s") or {}).get(
        TRAIN_MODULE)


def device_us_per_step(ctx: dict):
    """Device seconds of the training module per task trained on, in
    microseconds."""
    s, steps = _module_s(ctx), _counter(ctx, "train_steps")
    return 1e6 * s / steps if s and steps else None


def td_update_share(ctx: dict):
    """Per cent of the training module's device seconds spent in the TD
    kernel."""
    s, k = _module_s(ctx), (ctx.get("trace") or {}).get("kernel_s")
    return 100.0 * k / s if s and k else None


def td_update_roofline(ctx: dict):
    """The TD kernel's share of its roofline: the least time its
    operations and bytes take at the chip's peaks over its device
    seconds, for the updates of the profiled window."""
    k = (ctx.get("trace") or {}).get("kernel_s")
    n, peak = _counter(ctx, "td_updates"), ctx.get("peak")
    if not k or not n or not peak:
        return None
    cost = _td_cost(ctx)
    share, _ = roofline_share(cost["flops"] * n, cost["bytes"] * n, k,
                              peak["bf16_flops"] * ctx["chips"],
                              peak["hbm_bytes_per_s"] * ctx["chips"])
    return share


def train_mfu(ctx: dict):
    """Model FLOPs of training per second over the chips' bf16 peak (per
    cent): each decision's acting Q-net forward and its share of the TD
    updates, at the window's ``decisions_per_s``."""
    d, w, peak = ctx.get("decisions"), ctx.get("window_s"), ctx.get("peak")
    if not d or not w or not peak or "td_updates" not in ctx:
        return None
    q = ctx["config"]["qnet"]
    per_step = (qnet_forward_flops(q["state_dim"], q["hidden"],
                                   q["n_actions"])
                + _td_cost(ctx)["flops"] * ctx["td_updates"] / d)
    return 100.0 * per_step * d / w / (peak["bf16_flops"] * ctx["chips"])
