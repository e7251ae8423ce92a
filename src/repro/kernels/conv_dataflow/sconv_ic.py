"""SconvIC — SSconv-IP-CR archetype (ShiDianNao) as a Pallas TPU kernel.

Taxonomy mapping (DESIGN.md "TPU taxonomy adaptation"):
  * SSconv: each BasicUnit iteration covers PART of a 2D convolution —
    the grid tiles the OUTPUT rows, so one invocation computes one
    output-row band (a sub-rectangle of the conv).
  * IP (ifmaps propagate): the ifmap row *window* for the band is
    VMEM-resident and read at kh*kw shifted offsets — the shift-register
    ifmap propagation between PEs becomes shifted slices of the window.
  * CR (concentrated registers, never psums): the OUTPUT band is the
    stationary operand (each "PE" owns one output neuron, ShiDianNao
    style); psums never leave the accumulator until the band is done.

Each tap contracts all input channels at once,
``[row_tile*Wo, Cin] @ [Cin, Cout]``, so the kernel body is ``kh*kw``
matmuls whatever the channel count.

VMEM residency is **bounded**: each grid step DMAs its own
``row_tile + kh - 1`` row window (the band's rows plus the ``kh - 1``
halo rows shared with the next band) from the un-blocked ifmap
(``memory_space=ANY``) into a fixed scratch buffer, so arbitrarily tall
ifmaps stream through the same window.

The output-row grid does not require ``row_tile | ho``: the host pads H
so the band grid covers ``ceil(ho / row_tile)`` full tiles, the tail
band computes on zero rows (every DMA stays in-bounds by construction)
and the caller slices the pad rows off.

Grid: (N, Ho_tiles) — fully parallel; no cross-step accumulation
(contrast with SconvOD, where psums flow across sequential grid steps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.conv_dataflow.tiling import (SUBLANE, VMEM_LIMIT_BYTES,
                                                pad_plane, tap_matmul)


def _kernel(x_hbm, w_ref, o_ref, xwin_ref, sem, *, kh: int, kw: int,
            row_tile: int, wo: int):
    b = pl.program_id(0)
    r = pl.program_id(1)
    # halo window DMA: this band's row_tile rows + kh-1 shared halo rows
    copy = pltpu.make_async_copy(
        x_hbm.at[b, pl.ds(r * row_tile, row_tile + kh - 1)], xwin_ref, sem)
    copy.start()
    copy.wait()
    o_ref[...] = tap_matmul(xwin_ref, w_ref, kh, kw, 0, row_tile,
                            wo).astype(o_ref.dtype)


def sconv_ic(x: jax.Array, w: jax.Array, *, row_tile: int = SUBLANE,
             interpret: bool = False) -> jax.Array:
    """x [N,H,W,Cin], w [KH,KW,Cin,Cout] -> [N,Ho,Wo,Cout] (stride 1, VALID)."""
    n, h, _, cin = x.shape
    kh, kw, _, cout = w.shape
    row_tile = min(row_tile, h - kh + 1)
    # tail band: pad H so every window DMA is in-bounds (the padded output
    # rows are computed on zero rows and sliced off below); the DMA'd
    # window keeps whole (8, 128) tiles, so W and Cin pad to them too
    x, ho, wo, ho_pad, wo_pad = pad_plane(x, kh, kw, row_tile,
                                          dma_aligned=True)
    w = jnp.pad(w, ((0, 0), (0, 0), (0, x.shape[3] - cin), (0, 0)))
    nb = ho_pad // row_tile
    wd, cin = x.shape[2], x.shape[3]

    out = pl.pallas_call(
        functools.partial(_kernel, kh=kh, kw=kw, row_tile=row_tile,
                          wo=wo_pad),
        grid=(n, nb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((kh, kw, cin, cout), lambda b, r: (0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, row_tile * wo_pad, cout),
                               lambda b, r: (b, r, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho_pad * wo_pad, cout), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((row_tile + kh - 1, wd, cin), x.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sconv_ic",
    )(x, w)
    return out.reshape(n, ho_pad, wo_pad, cout)[:, :ho, :wo]
