"""Fused DQN TD-update as a single Pallas kernel.

The dataflow lesson of the HMAI conv kernels (and of Liu et al.'s
dataflow accelerator, arXiv:2109.07047) applied to the trainer's compute
floor: the p0..p5 MLP (two ReLU layers + linear head, a few hundred KB)
stays **resident in VMEM** while the [B, D] replay batch **streams**
through a sequential grid of row tiles.  One kernel invocation covers
what the XLA path spreads over a dozen HBM-bouncing ops:

  1. EvalNet forward on ``s``   (residuals z1/h1/z2/h2 kept in registers)
  2. double-DQN target: EvalNet argmax on ``s_next`` (first-max
     tie-break, computed as a min over matching lane indices — no
     ``argmax`` primitive needed), TargNet values the chosen action
  3. Huber TD loss against ``y = r + gamma * (1 - done) * q_tn``
     (``y`` is a constant of the backward pass, exactly like the
     oracle's ``stop_gradient``)
  4. hand-derived backward (see below) accumulated into VMEM scratch
     across batch tiles
  5. at the last tile: global-norm clip at 10.0, and either the clipped
     gradients are emitted (``fold_adam=False`` — the DP trainer
     ``pmean``s them before a shared Adam step) or Adam is applied in
     the same kernel (``fold_adam=True`` — the single-shard fast path).

Backward derivation (per sample, mask m in {0,1} for padded tail rows;
the 1/B of the mean loss is folded into g):

    g    = -(m / B) * clip(err, -1, 1)        # dL/dq_sel, Huber delta=1
    dq   = g * onehot(a)                      # [bt, A]
    dW3 += h2^T dq        db3 += sum_rows dq
    dh2  = (dq W3^T) * [z2 > 0]               # relu' (0 at z == 0, as
    dW2 += h1^T dh2       db2 += sum_rows dh2 #  jax.nn.relu's custom jvp)
    dh1  = (dh2 W2^T) * [z1 > 0]
    dW1 += s^T dh1        db1 += sum_rows dh1

Masked rows have err = 0, hence g = 0, hence zero contribution to every
accumulator — the tail block computes and discards, it never corrupts.

VMEM residency: params (12 tensors), one [bt, D] batch tile x 5, the six
gradient accumulators and a (1, 1) loss accumulator — bounded in B, so
arbitrarily long replay batches stream through a fixed footprint.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the contractions run at the XLA Q-net's precision (HIGHEST), so the
# compiled kernel tracks it to accumulation rounding
from repro.core.flexai.dqn import PRECISION


GRAD_CLIP = 10.0
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _forward(s, w1, b1, w2, b2, w3, b3):
    """2xReLU MLP + linear head, returning pre-activations for relu'."""
    z1 = jax.lax.dot(s, w1, precision=PRECISION,
                     preferred_element_type=jnp.float32) + b1
    h1 = jnp.maximum(z1, 0.0)
    z2 = jax.lax.dot(h1, w2, precision=PRECISION,
                     preferred_element_type=jnp.float32) + b2
    h2 = jnp.maximum(z2, 0.0)
    q = jax.lax.dot(h2, w3, precision=PRECISION,
                    preferred_element_type=jnp.float32) + b3
    return z1, h1, z2, h2, q


def _bdot(a, b):
    """[bt, M]^T @ [bt, N] -> [M, N] batch-contraction (MXU-friendly)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)


def _td_kernel(*refs, bt: int, B: int, gamma: float, lr: float,
               fold_adam: bool):
    s_ref, a_ref, r_ref, sn_ref, dn_ref = refs[:5]
    ew = [r[...] for r in refs[5:11]]       # eval w1 b1 w2 b2 w3 b3
    tw = [r[...] for r in refs[11:17]]      # targ
    k = 17
    if fold_adam:
        mu_refs, nu_refs = refs[k:k + 6], refs[k + 6:k + 12]
        corr_ref = refs[k + 12]
        k += 13
    loss_ref = refs[k]
    out_refs = refs[k + 1:k + 7]            # grads OR new params
    k += 7
    if fold_adam:
        outm_refs, outv_refs = refs[k:k + 6], refs[k + 6:k + 12]
        k += 12
    acc_refs, lacc_ref = refs[k:k + 6], refs[k + 6]

    i = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        for a in acc_refs:
            a[...] = jnp.zeros_like(a)
        lacc_ref[...] = jnp.zeros_like(lacc_ref)

    # ---- tile contribution -------------------------------------------
    n_actions = ew[4].shape[1]
    rows = i * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
    msk = (rows < B).astype(jnp.float32)            # padded-tail mask
    s = s_ref[...]
    sn = sn_ref[...]

    z1, h1, z2, h2, q = _forward(s, *ew)            # EvalNet(s)
    _, _, _, _, qn_e = _forward(sn, *ew)            # EvalNet(s') — argmax
    _, _, _, _, qn_t = _forward(sn, *tw)            # TargNet(s') — value

    lane = jax.lax.broadcasted_iota(jnp.int32, (bt, n_actions), 1)
    # first-max tie-break == jnp.argmax: min lane index attaining the max
    a_star = jnp.min(
        jnp.where(qn_e == jnp.max(qn_e, axis=-1, keepdims=True),
                  lane, n_actions), axis=-1, keepdims=True)
    q_tn = jnp.sum(qn_t * (lane == a_star).astype(jnp.float32),
                   axis=-1, keepdims=True)          # [bt, 1]
    oh_a = (lane == a_ref[...]).astype(jnp.float32)
    q_sel = jnp.sum(q * oh_a, axis=-1, keepdims=True)

    y = r_ref[...] + gamma * (1.0 - dn_ref[...]) * q_tn
    err = (y - q_sel) * msk                         # masked rows: err = 0
    abse = jnp.abs(err)
    huber = jnp.where(abse <= 1.0, 0.5 * err * err, abse - 0.5)
    lacc_ref[...] += jnp.sum(huber)[None, None]

    g = -(1.0 / B) * jnp.clip(err, -1.0, 1.0)       # dL/dq_sel
    dq = g * oh_a
    dh2 = jax.lax.dot_general(dq, ew[4], (((1,), (1,)), ((), ())),
                              precision=PRECISION,
                              preferred_element_type=jnp.float32) \
        * (z2 > 0.0).astype(jnp.float32)
    dh1 = jax.lax.dot_general(dh2, ew[2], (((1,), (1,)), ((), ())),
                              precision=PRECISION,
                              preferred_element_type=jnp.float32) \
        * (z1 > 0.0).astype(jnp.float32)
    acc_refs[0][...] += _bdot(s, dh1)               # dW1
    acc_refs[1][...] += jnp.sum(dh1, axis=0, keepdims=True)
    acc_refs[2][...] += _bdot(h1, dh2)              # dW2
    acc_refs[3][...] += jnp.sum(dh2, axis=0, keepdims=True)
    acc_refs[4][...] += _bdot(h2, dq)               # dW3
    acc_refs[5][...] += jnp.sum(dq, axis=0, keepdims=True)

    # ---- finalize: clip, then emit grads or fold Adam ----------------
    @pl.when(i == nb - 1)
    def _finalize():
        loss_ref[...] = lacc_ref[...] / B
        sq = jnp.float32(0.0)
        for a in acc_refs:
            sq += jnp.sum(a[...] * a[...])
        gnorm = jnp.sqrt(sq)
        clip = jnp.minimum(1.0, GRAD_CLIP / jnp.maximum(gnorm, 1e-9))
        if not fold_adam:
            for o, a in zip(out_refs, acc_refs):
                o[...] = a[...] * clip
        else:
            # Adam bias corrections, computed outside: Mosaic has no pow
            c1, c2 = corr_ref[:, 0:1], corr_ref[:, 1:2]
            for p, m_r, v_r, a, op, om, ov in zip(
                    refs[5:11], mu_refs, nu_refs, acc_refs,
                    out_refs, outm_refs, outv_refs):
                gg = a[...] * clip
                m = ADAM_B1 * m_r[...] + (1.0 - ADAM_B1) * gg
                v = ADAM_B2 * v_r[...] + (1.0 - ADAM_B2) * gg * gg
                om[...] = m
                ov[...] = v
                op[...] = p[...] - lr * (m / c1) / (jnp.sqrt(v / c2)
                                                   + ADAM_EPS)


def dqn_td_pallas(s, a, r, sn, done, eval_w, targ_w, *, gamma: float,
                  batch_tile: int, interpret: bool,
                  adam=None, lr: float = 0.0):
    """Raw kernel entry point over 2-D operands.

    s/sn [B, D] f32, a [B, 1] i32, r/done [B, 1] f32; ``eval_w``/
    ``targ_w`` are 6-tuples (w1 [D,H1], b1 [1,H1], w2, b2, w3, b3 [1,A]).
    Returns ``(loss [1,1], grads 6-tuple)`` — or, with ``adam=(mu6, nu6,
    corr [1,2] f32)`` (the step's bias corrections ``1 - b1**t``,
    ``1 - b2**t``), ``(loss, new_params 6-tuple, new_mu, new_nu)``.
    """
    B, d = s.shape
    fold_adam = adam is not None
    bt = min(batch_tile, B)
    nb = pl.cdiv(B, bt)
    bp = nb * bt
    if bp != B:
        pad = ((0, bp - B), (0, 0))
        s, a, r, sn, done = (jnp.pad(x, pad) for x in (s, a, r, sn, done))

    pshapes = [w.shape for w in eval_w]
    batch_dims = [d, 1, 1, d, 1]

    def bspec(dim):
        return pl.BlockSpec((bt, dim), lambda i: (i, 0))

    def pspec(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    in_specs = [bspec(dim) for dim in batch_dims]
    in_specs += [pspec(sh) for sh in pshapes] * 2
    inputs = [s, a, r, sn, done, *eval_w, *targ_w]
    if fold_adam:
        mu, nu, corr = adam
        in_specs += [pspec(sh) for sh in pshapes] * 2 \
            + [pspec((1, 2))]
        inputs += [*mu, *nu, corr]

    out_specs = [pspec((1, 1))] + [pspec(sh) for sh in pshapes]
    out_shape = [jax.ShapeDtypeStruct((1, 1), jnp.float32)] \
        + [jax.ShapeDtypeStruct(sh, jnp.float32) for sh in pshapes]
    if fold_adam:
        out_specs += [pspec(sh) for sh in pshapes] * 2
        out_shape += [jax.ShapeDtypeStruct(sh, jnp.float32)
                      for sh in pshapes] * 2

    scratch = [pltpu.VMEM(sh, jnp.float32) for sh in pshapes] \
        + [pltpu.VMEM((1, 1), jnp.float32)]

    outs = pl.pallas_call(
        functools.partial(_td_kernel, bt=bt, B=B, gamma=gamma, lr=lr,
                          fold_adam=fold_adam),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="dqn_td_update" if fold_adam else "dqn_td_grads",
    )(*inputs)

    loss = outs[0]
    if not fold_adam:
        return loss, tuple(outs[1:7])
    return loss, tuple(outs[1:7]), tuple(outs[7:13]), tuple(outs[13:19])
