"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid (B*H, nChunks) with chunks sequential; the inter-chunk SSD state
[P, N] lives in VMEM scratch, so the recurrence never round-trips HBM.
Within a chunk everything is matmul-shaped (the SSD duality): the decay
matrix L, the C·Bᵀ score block, and the state update are MXU work.

B/C projections are shared across heads (ngroups=1); the wrapper indexes
them with g // H inside the BlockSpec index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, a_ref, b_ref, c_ref, y_ref, sfin_ref, state_ref, *,
            chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[...].astype(jnp.float32)       # [Q, P]
    a = a_ref[...][:, 0].astype(jnp.float32)  # [Q]
    Bm = b_ref[...].astype(jnp.float32)      # [Q, N]
    Cm = c_ref[...].astype(jnp.float32)      # [Q, N]

    a_cum = jnp.cumsum(a)                    # [Q]
    # intra-chunk decay matrix L[i,j] = exp(a_cum[i]-a_cum[j]) for i >= j
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    diff = a_cum[:, None] - a_cum[None, :]
    L = jnp.where(rows >= cols, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)   # [Q, Q]
    y_diag = jax.lax.dot(scores * L, u,
                         preferred_element_type=jnp.float32)  # [Q, P]

    s_prev = state_ref[...]                  # [N, P]
    in_decay = jnp.exp(a_cum)                # [Q]
    y_off = jax.lax.dot(Cm * in_decay[:, None], s_prev,
                        preferred_element_type=jnp.float32)   # [Q, P]

    decay_end = jnp.exp(a_cum[-1] - a_cum)   # [Q]
    s_chunk = jax.lax.dot_general(
        Bm * decay_end[:, None], u, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [N, P]
    state_ref[...] = s_chunk + jnp.exp(a_cum[-1]) * s_prev

    y_ref[...] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _flush():
        sfin_ref[...] = state_ref[...].astype(sfin_ref.dtype)


def ssd_scan_flat(u: jax.Array, a: jax.Array, Bm: jax.Array, Cm: jax.Array,
                  *, chunk: int = 128, n_heads: int = 1,
                  interpret: bool = False):
    """u [G, S, P]; a [G, S]; Bm/Cm [G//n_heads, S, N] (head-shared).

    Returns (y [G, S, P], final_state [G, N, P]).
    """
    g, s, p = u.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    grid = (g, s // chunk)

    y, sfin = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, chunk, p), lambda gi, ci: (gi, ci, 0)),
            pl.BlockSpec((None, chunk, 1), lambda gi, ci: (gi, ci, 0)),
            pl.BlockSpec((None, chunk, n),
                         lambda gi, ci: (gi // n_heads, ci, 0)),
            pl.BlockSpec((None, chunk, n),
                         lambda gi, ci: (gi // n_heads, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, p), lambda gi, ci: (gi, ci, 0)),
            pl.BlockSpec((None, n, p), lambda gi, ci: (gi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, s, p), u.dtype),
            jax.ShapeDtypeStruct((g, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(u, a[..., None], Bm, Cm)
    return y, sfin
