"""Operations and bytes of the model's work, from its shapes.

A multiply-add counts as two operations.  Bytes are what a kernel must
move between device memory and the chip at the least: each input and
output array once, float32 (4 bytes) unless stated.
"""
from __future__ import annotations


def mlp_forward_flops(widths) -> int:
    """One row through dense layers ``widths[0] -> ... -> widths[-1]``:
    the matmuls (2 per weight) plus bias adds and ReLUs (one each per
    hidden unit, one bias add per output)."""
    flops = 0
    for i, (a, b) in enumerate(zip(widths[:-1], widths[1:])):
        flops += 2 * a * b + b                      # matmul + bias
        if i < len(widths) - 2:
            flops += b                              # ReLU
    return flops


def mlp_param_count(widths) -> int:
    return sum(a * b + b for a, b in zip(widths[:-1], widths[1:]))


def qnet_widths(state_dim: int, hidden, n_actions: int) -> list:
    return [state_dim, *hidden, n_actions]


def qnet_forward_flops(state_dim: int, hidden, n_actions: int) -> int:
    """FLOPs of one greedy placement decision's Q-net forward."""
    return mlp_forward_flops(qnet_widths(state_dim, hidden, n_actions))


def td_update_cost(batch: int, state_dim: int, hidden,
                   n_actions: int) -> dict:
    """Operations and bytes of one fused double-DQN TD update on a replay
    batch of ``batch`` rows: EvalNet forward on ``s`` and on ``s_next``,
    TargNet forward on ``s_next``, the backward pass of the ``s``
    forward (two matmuls per layer but the first layer's input
    gradient, which is not needed), the global-norm clip (2 per
    parameter) and Adam (about 10 per parameter).

    Bytes: the batch (``s``, ``s_next``, ``a``, ``r``, ``done``), both
    networks' parameters, Adam's two moments read, and the new
    parameters and moments written."""
    widths = qnet_widths(state_dim, hidden, n_actions)
    fwd = mlp_forward_flops(widths)
    mm = [2 * a * b for a, b in zip(widths[:-1], widths[1:])]
    bwd = 2 * sum(mm) - mm[0]
    params = mlp_param_count(widths)
    flops = batch * (3 * fwd + bwd) + 12 * params
    batch_bytes = batch * (2 * state_dim * 4 + 4 + 4 + 4)
    param_bytes = 4 * params
    nbytes = batch_bytes + 2 * param_bytes + 2 * param_bytes \
        + 3 * param_bytes
    return {"flops": int(flops), "bytes": int(nbytes)}


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes_per_s: float):
    """Least time the chip could take (the larger of the compute and the
    memory bound) over the measured time, in percent, and which bound
    sets it.  None where nothing was measured."""
    if seconds <= 0:
        return None
    t_compute = flops / peak_flops
    t_memory = nbytes / peak_bytes_per_s
    bound = "compute" if t_compute >= t_memory else "memory"
    return 100.0 * max(t_compute, t_memory) / seconds, bound
