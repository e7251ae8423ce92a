"""DQN networks for FlexAI (paper §7.1).

EvalNet / TargNet: identical MLPs of two fully-connected layers (256, 64
neurons, ReLU) followed by a linear head producing one Q value per
accelerator.  TargNet's parameters are copied from EvalNet every
``target_sync_every`` updates; the TD loss is

    L = ( r + gamma * max_a' TargNet(s')  -  EvalNet(s)[a] )^2

exactly the §7.1 formulation.  The update step is a single jitted function.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


class DQNParams(NamedTuple):
    w1: jax.Array
    b1: jax.Array
    w2: jax.Array
    b2: jax.Array
    w3: jax.Array
    b3: jax.Array


HIDDEN = (256, 64)

# f32 matmuls at full precision on every backend.  At the default a TPU
# runs [M >= 2, D] dots as one bf16 MXU pass but strength-reduces a
# one-row [1, D] dot to an exact f32 VPU reduction, so the greedy argmax
# would depend on how many wave lanes share a device.
PRECISION = jax.lax.Precision.HIGHEST


def init_qnet(key, state_dim: int, n_actions: int) -> DQNParams:
    k1, k2, k3 = jax.random.split(key, 3)
    s1, s2 = HIDDEN

    def glorot(k, fan_in, fan_out):
        lim = jnp.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(k, (fan_in, fan_out), jnp.float32,
                                  -lim, lim)

    return DQNParams(
        w1=glorot(k1, state_dim, s1), b1=jnp.zeros((s1,)),
        w2=glorot(k2, s1, s2), b2=jnp.zeros((s2,)),
        w3=glorot(k3, s2, n_actions), b3=jnp.zeros((n_actions,)),
    )


def qnet_apply(p: DQNParams, state: jax.Array) -> jax.Array:
    """state [..., state_dim] -> Q values [..., n_actions]."""
    h = jax.nn.relu(jnp.matmul(state, p.w1, precision=PRECISION) + p.b1)
    h = jax.nn.relu(jnp.matmul(h, p.w2, precision=PRECISION) + p.b2)
    return jnp.matmul(h, p.w3, precision=PRECISION) + p.b3


class AdamState(NamedTuple):
    step: jax.Array
    mu: DQNParams
    nu: DQNParams


def _adam_init(params: DQNParams) -> AdamState:
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return AdamState(jnp.zeros((), jnp.int32), z, z)


def dqn_td_grads(eval_p: DQNParams, targ_p: DQNParams, batch: dict,
                 gamma: float = 0.95):
    """TD loss + norm-clipped gradients on a replay batch — the gradient
    half of :func:`dqn_td_update`, split out so the data-parallel trainer
    can all-reduce (``lax.pmean``) the clipped gradients across route
    shards before the shared Adam application.

    batch: s [B,D], a [B], r [B], s_next [B,D], done [B].
    Returns (loss, grads) with the 10.0 global-norm clip already applied
    (clip-then-average: each shard clips its local batch's gradient, so a
    single diverging shard cannot blow up the synchronized step).
    """

    def loss_fn(p):
        q = qnet_apply(p, batch["s"])                        # [B, A]
        q_sel = jnp.take_along_axis(q, batch["a"][:, None], axis=1)[:, 0]
        # double DQN (van Hasselt et al. — the paper's [12]): EvalNet picks
        # the argmax action, TargNet values it
        a_star = jnp.argmax(qnet_apply(p, batch["s_next"]), axis=-1)
        q_next = qnet_apply(targ_p, batch["s_next"])         # [B, A]
        q_tn = jnp.take_along_axis(q_next, a_star[:, None], axis=1)[:, 0]
        y = batch["r"] + gamma * (1.0 - batch["done"]) * q_tn
        y = jax.lax.stop_gradient(y)
        # Huber (smooth-L1) — standard DQN stabilizer vs outlier TD errors
        err = y - q_sel
        delta = 1.0
        return jnp.mean(jnp.where(
            jnp.abs(err) <= delta, 0.5 * err * err,
            delta * (jnp.abs(err) - 0.5 * delta)))

    loss, grads = jax.value_and_grad(loss_fn)(eval_p)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                         for g in jax.tree_util.tree_leaves(grads)))
    clip = jnp.minimum(1.0, 10.0 / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
    return loss, grads


def adam_apply(eval_p: DQNParams, opt: AdamState, grads: DQNParams,
               lr: float = 0.01):
    """The Adam half of :func:`dqn_td_update`: one optimizer step on
    already-clipped (and, in the DP trainer, already all-reduced)
    gradients.  Returns (new_eval_p, new_opt)."""
    step = opt.step + 1
    b1, b2, eps = 0.9, 0.999, 1e-8
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v

    results = [upd(p, g, m, v) for p, g, m, v
               in zip(eval_p, grads, opt.mu, opt.nu)]
    new_p = DQNParams(*[r[0] for r in results])
    new_m = DQNParams(*[r[1] for r in results])
    new_v = DQNParams(*[r[2] for r in results])
    return new_p, AdamState(step, new_m, new_v)


def dqn_td_update(eval_p: DQNParams, targ_p: DQNParams, opt: AdamState,
                  batch: dict, gamma: float = 0.95, lr: float = 0.01):
    """One TD update on a replay batch — pure (unjitted), so the scan
    engine can inline it in a ``lax.scan`` body.

    batch: s [B,D], a [B], r [B], s_next [B,D], done [B].
    Returns (new_eval_p, new_opt, loss).
    """
    loss, grads = dqn_td_grads(eval_p, targ_p, batch, gamma=gamma)
    new_p, new_opt = adam_apply(eval_p, opt, grads, lr=lr)
    return new_p, new_opt, loss


@functools.partial(jax.jit, static_argnames=("gamma", "lr"))
def dqn_update(eval_p: DQNParams, targ_p: DQNParams, opt: AdamState,
               batch: dict, *, gamma: float = 0.95, lr: float = 0.01):
    """Jitted host-loop entry point around ``dqn_td_update``."""
    return dqn_td_update(eval_p, targ_p, opt, batch, gamma=gamma, lr=lr)


def save_dqn_npz(path: str, params: DQNParams) -> None:
    """THE checkpoint format (p0..p5 EvalNet arrays) — shared by
    ``FlexAIAgent`` and ``ScanFlexAI`` so the loop and fused trainers
    stay freely interchangeable."""
    import numpy as np
    np.savez(path, **{f"p{i}": np.asarray(w)
                      for i, w in enumerate(params)})


def load_dqn_npz(path: str) -> DQNParams:
    import numpy as np
    data = np.load(path)
    return DQNParams(*[jnp.asarray(data[f"p{i}"])
                       for i in range(len(data.files))])


class DQNLearner:
    """EvalNet + TargNet + Adam + target syncing (host-side wrapper)."""

    def __init__(self, key, state_dim: int, n_actions: int,
                 gamma: float = 0.95, lr: float = 0.01,
                 target_sync_every: int = 100):
        self.eval_p = init_qnet(key, state_dim, n_actions)
        self.targ_p = self.eval_p
        self.opt = _adam_init(self.eval_p)
        self.gamma = gamma
        self.lr = lr
        self.target_sync_every = target_sync_every
        self.updates = 0
        self._q_jit = jax.jit(qnet_apply)

    def q_values(self, state) -> jax.Array:
        return self._q_jit(self.eval_p, state)

    def update(self, batch: dict) -> float:
        self.eval_p, self.opt, loss = dqn_update(
            self.eval_p, self.targ_p, self.opt, batch,
            gamma=self.gamma, lr=self.lr)
        self.updates += 1
        if self.updates % self.target_sync_every == 0:
            self.targ_p = self.eval_p
        return float(loss)
