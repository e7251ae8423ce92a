"""SconvOD — Sconv-OP-DR archetype (NeuFlow) as a Pallas TPU kernel.

Taxonomy mapping (DESIGN.md "TPU taxonomy adaptation"):
  * Sconv: whole 2D convolutions — every grid step covers the full output
    plane for one slice of input channels.
  * OP (ofmaps propagate): partial sums accumulate ACROSS sequential grid
    steps over input channels — the VMEM accumulator plays the role of the
    PE->PE psum FIFO chain.
  * DR (dispersive registers): the filter taps for the current channel
    slice stay resident (weight-stationary) while the ifmap streams —
    per-PE weight registers become the resident VMEM filter block.

Each tap contracts the channel slice on the MXU,
``[Ho*Wo, Cin_tile] @ [Cin_tile, Cout]``; what separates this kernel from
MconvMC is its grid and what stays resident, not the MAC unit.

Grid: (N, Cin_tiles) with the channel dim sequential ("arbitrary").
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
    ci_step = pl.program_id(1)

    @pl.when(ci_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    accumulate_plane(x_ref, w_ref, acc_ref, kh, kw, wo)

    @pl.when(ci_step == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def sconv_od(x: jax.Array, w: jax.Array, *, cin_tile: int = LANE,
             interpret: bool = False) -> jax.Array:
    """x [N,H,W,Cin], w [KH,KW,Cin,Cout] -> [N,Ho,Wo,Cout] (stride 1, VALID).

    Compiled for a TPU, ``cin_tile`` must be a multiple of 128 or cover
    the whole channel axis (the lane dim of the ifmap block)."""
    n, _, _, cin = x.shape
    kh, kw, _, cout = w.shape
    # the channel grid covers ceil(cin / cin_tile) full tiles: a channel
    # count the tile does not divide zero-pads to the next tile boundary
    # (zero ifmap channels contribute exactly nothing to the accumulator)
    cin_tile = min(cin_tile, cin)
    n_ci = pl.cdiv(cin, cin_tile)
    cin_pad = n_ci * cin_tile
    if cin_pad != cin:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, cin_pad - cin)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, cin_pad - cin), (0, 0)))
    x, ho, wo, ho_pad, wo_pad = pad_plane(x, kh, kw)
    h, wd = x.shape[1], x.shape[2]

    out = pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, wo=wo_pad),
        grid=(n, n_ci),
        in_specs=[
            pl.BlockSpec((None, h, wd, cin_tile),
                         lambda b, c: (b, 0, 0, c)),
            pl.BlockSpec((kh, kw, cin_tile, cout),
                         lambda b, c: (0, 0, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, ho_pad * wo_pad, cout),
                               lambda b, c: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho_pad * wo_pad, cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((ho_pad * wo_pad, cout), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sconv_od",
    )(x, w)
    return out.reshape(n, ho_pad, wo_pad, cout)[:, :ho, :wo]
