"""Drop-in fused TD-update entry points.

``dqn_td_grads_fused`` / ``dqn_td_update_fused`` mirror the signatures of
:func:`repro.core.flexai.dqn.dqn_td_grads` / ``dqn_td_update`` exactly, so
the engine swaps them in behind ``ScanFlexAI(td_kernel=True)`` without
touching the ``(loss, grads)`` / ``adam_apply`` seam:

* the grads variant emits *clipped* gradients — the DP trainer still
  ``ravel_pytree``s and ``lax.pmean``s them across route shards before a
  shared :func:`adam_apply`, exactly as with the XLA oracle;
* the update variant folds the Adam step into the same kernel pass (the
  single-shard fast path); the ``AdamState.step`` counter increments
  host-side, matching ``adam_apply``.

This layer owns the batch-dict plumbing: 1-D replay fields reshape to the
2-D layouts Mosaic wants ([B] -> [B, 1], biases [H] -> [1, H]) and back.
Batch padding to the tile grid lives in ``kernel.py`` (masked tail
blocks).  ``interpret=None`` defers to
:func:`repro.compat.pallas_interpret_default`: interpreted on the CPU
backend, compiled on any other (see ``repro.kernels.protocol``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.compat import pallas_interpret_default
from repro.core.flexai.dqn import AdamState, DQNParams

from .kernel import ADAM_B1, ADAM_B2, dqn_td_pallas

# Default batch-row tile: one tile covers the engine's replay batches
# (FlexAIConfig.batch_size <= 128 everywhere in the repo), so the grid is
# a single step and accumulation order matches the oracle's single matmul.
BATCH_TILE = 128


def _batch_2d(batch: dict):
    s = jnp.asarray(batch["s"], jnp.float32)
    b = s.shape[0]
    return (s,
            jnp.asarray(batch["a"], jnp.int32).reshape(b, 1),
            jnp.asarray(batch["r"], jnp.float32).reshape(b, 1),
            jnp.asarray(batch["s_next"], jnp.float32),
            jnp.asarray(batch["done"], jnp.float32).reshape(b, 1))


def _params_2d(p: DQNParams):
    return (p.w1, p.b1.reshape(1, -1), p.w2, p.b2.reshape(1, -1),
            p.w3, p.b3.reshape(1, -1))


def _params_back(flat, like: DQNParams) -> DQNParams:
    return DQNParams(flat[0], flat[1].reshape(like.b1.shape),
                     flat[2], flat[3].reshape(like.b2.shape),
                     flat[4], flat[5].reshape(like.b3.shape))


def dqn_td_grads_fused(eval_p: DQNParams, targ_p: DQNParams, batch: dict,
                       gamma: float = 0.95, *, batch_tile: int = BATCH_TILE,
                       interpret: bool | None = None):
    """Fused-kernel counterpart of :func:`dqn.dqn_td_grads`.

    Returns ``(loss, grads)`` with the 10.0 global-norm clip applied —
    the DP trainer's pmean seam consumes this unchanged.
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    s, a, r, sn, dn = _batch_2d(batch)
    loss, grads = dqn_td_pallas(
        s, a, r, sn, dn, _params_2d(eval_p), _params_2d(targ_p),
        gamma=gamma, batch_tile=batch_tile, interpret=interpret)
    return loss[0, 0], _params_back(grads, eval_p)


def dqn_td_update_fused(eval_p: DQNParams, targ_p: DQNParams,
                        opt: AdamState, batch: dict, gamma: float = 0.95,
                        lr: float = 0.01, *, batch_tile: int = BATCH_TILE,
                        interpret: bool | None = None):
    """Fused-kernel counterpart of :func:`dqn.dqn_td_update` — gradients
    AND the Adam step in one kernel pass (single-shard path).

    Returns ``(new_eval_p, new_opt, loss)``.
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    s, a, r, sn, dn = _batch_2d(batch)
    mu = _params_2d(opt.mu)
    nu = _params_2d(opt.nu)
    # the bias corrections exactly as adam_apply computes them
    t = (opt.step + 1).astype(jnp.float32)
    corr = jnp.stack([1.0 - ADAM_B1 ** t, 1.0 - ADAM_B2 ** t]).reshape(1, 2)
    loss, new_p, new_mu, new_nu = dqn_td_pallas(
        s, a, r, sn, dn, _params_2d(eval_p), _params_2d(targ_p),
        gamma=gamma, batch_tile=batch_tile, interpret=interpret,
        adam=(mu, nu, corr), lr=lr)
    new_opt = AdamState(opt.step + 1,
                        _params_back(new_mu, eval_p),
                        _params_back(new_nu, eval_p))
    return _params_back(new_p, eval_p), new_opt, loss[0, 0]
