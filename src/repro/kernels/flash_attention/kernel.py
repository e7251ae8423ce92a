"""Flash attention (block-wise online softmax) as a Pallas TPU kernel.

Grid (B*H, nQ, nKV) with the KV dimension sequential; per-(head, q-block)
VMEM scratch carries the running max / normalizer / accumulator.  Causal
blocks strictly above the diagonal are SKIPPED via ``pl.when`` — unlike
the XLA fallback (``models.attention.chunked_attention``), which must
compute-and-mask them.  This kernel is the TPU fast path; the dry-run on
the CPU host platform measures the fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip kv blocks strictly above the diagonal
    needed = (j * block_k <= i * block_q + block_q - 1) if causal else True

    @pl.when(needed)
    def _compute():
        q = q_ref[...].astype(jnp.float32)            # [bq, d]
        k = k_ref[...].astype(jnp.float32)            # [bk, d]
        v = v_ref[...].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...][:, 0]                      # [bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = (l_ref[...][:, 0] * alpha + jnp.sum(p, axis=1))[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]

    @pl.when(j == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_flat(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool, scale: float, block_q: int = 128,
                         block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q [G, Sq, D], k/v [G, Skv, D] (G = batch*heads, pre-broadcast)."""
    g, sq, d = q.shape
    skv = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    grid = (g, sq // block_q, skv // block_k)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # normalizer
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
