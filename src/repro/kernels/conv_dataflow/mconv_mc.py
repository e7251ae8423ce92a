"""MconvMC — Mconv-MP-CR archetype (Origami) as a Pallas TPU kernel.

Taxonomy mapping (DESIGN.md "TPU taxonomy adaptation"):
  * Mconv: each BasicUnit iteration processes MULTIPLE 2D convolutions —
    a [Tc (in-channel) x Tm (out-channel)] tile of channel pairs at once,
    as an im2col matrix multiplication on the MXU (Origami's matrix unit;
    Table 10's ">1 MAC per PE" + on-chip buffer).
  * MP (multiple propagation): both ifmap patches and filter tiles stream
    through the systolic array each step.
  * CR: psums live in a shared VMEM accumulator across the sequential
    in-channel grid dimension.

Grid: (N, Cout_tiles, Cin_tiles) with Cin sequential ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv_dataflow.tiling import (LANE, VMEM_LIMIT_BYTES,
                                                accumulate_plane, pad_plane)


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, kh: int, kw: int, wo: int):
    ci_step = pl.program_id(2)

    @pl.when(ci_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # im2col GEMM: each tap contributes [Ho*Wo, Tc] @ [Tc, Tm] on the MXU
    accumulate_plane(x_ref, w_ref, acc_ref, kh, kw, wo)

    @pl.when(ci_step == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def mconv_mc(x: jax.Array, w: jax.Array, *, cout_tile: int = LANE,
             cin_tile: int = LANE, interpret: bool = False) -> jax.Array:
    """x [N,H,W,Cin], w [KH,KW,Cin,Cout] -> [N,Ho,Wo,Cout] (stride 1, VALID).

    Compiled for a TPU, each tile must be a multiple of 128 or cover its
    whole channel axis (the lane dim of its block)."""
    n, _, _, cin = x.shape
    kh, kw, _, cout = w.shape
    # both channel grids cover whole tiles: a channel count a tile does not
    # divide zero-pads to the next tile boundary (zero ifmap channels add
    # nothing to a psum; zero filter columns give output channels that are
    # sliced off below)
    cout_tile = min(cout_tile, cout)
    cin_tile = min(cin_tile, cin)
    n_co, n_ci = pl.cdiv(cout, cout_tile), pl.cdiv(cin, cin_tile)
    cout_pad, cin_pad = n_co * cout_tile, n_ci * cin_tile
    if (cin_pad, cout_pad) != (cin, cout):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cin_pad - cin)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, cin_pad - cin),
                        (0, cout_pad - cout)))
    x, ho, wo, ho_pad, wo_pad = pad_plane(x, kh, kw)
    h, wd = x.shape[1], x.shape[2]

    out = pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, wo=wo_pad),
        grid=(n, n_co, n_ci),
        in_specs=[
            pl.BlockSpec((None, h, wd, cin_tile),
                         lambda b, co, ci: (b, 0, 0, ci)),
            pl.BlockSpec((kh, kw, cin_tile, cout_tile),
                         lambda b, co, ci: (0, 0, ci, co)),
        ],
        out_specs=pl.BlockSpec((None, ho_pad * wo_pad, cout_tile),
                               lambda b, co, ci: (b, 0, co)),
        out_shape=jax.ShapeDtypeStruct((n, ho_pad * wo_pad, cout_pad),
                                       x.dtype),
        scratch_shapes=[pltpu.VMEM((ho_pad * wo_pad, cout_tile), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="mconv_mc",
    )(x, w)
    return out.reshape(n, ho_pad, wo_pad, cout_pad)[:, :ho, :wo, :cout]
