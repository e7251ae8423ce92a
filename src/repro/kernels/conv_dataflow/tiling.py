"""Layout helpers shared by the three conv-dataflow kernels.

Mosaic lays a block's last two dims onto (sublane, lane) tiles of (8, 128)
for 32-bit data.  The kernels therefore flatten each tap's ifmap window to
a 2-D ``[rows * Wo, C]`` matrix, which needs ``Wo`` to be a multiple of 8:
the wrappers zero-pad the ifmap so the padded output plane is a whole
number of 8x8 tiles, and slice the pad off afterwards.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANE = 8
LANE = 128

# whole-plane accumulators at 416-pixel layer shapes (e.g. 104x104x217
# f32 = 9.4 MB, double-buffered output besides) outgrow Mosaic's default
# scoped-VMEM budget; v5e has 128 MiB of VMEM per core
VMEM_LIMIT_BYTES = 100 * 2**20

# the kernels' f32 MXU contractions run at full f32 precision, so compiled
# results agree with the float32 oracle to accumulation-order rounding
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def pad_plane(x: jax.Array, kh: int, kw: int, row_tile: int = SUBLANE,
              dma_aligned: bool = False):
    """Zero-pad ``x [N, H, W, C]`` at the bottom and right so the VALID
    output has a multiple of ``row_tile`` rows and of 8 columns.  With
    ``dma_aligned`` the ifmap itself is padded to whole (8, 128) tiles in
    (W, C), so a manual DMA of a row window slices whole tiles.  Returns
    ``(x, ho, wo, ho_pad, wo_pad)``; output rows and columns past
    ``(ho, wo)`` are computed on zeros and sliced off by the caller, and
    zero channels add nothing to a contraction (the caller pads the
    filter's Cin to match)."""
    _, h, w, c = x.shape
    ho, wo = h - kh + 1, w - kw + 1
    ho_pad = -(-ho // row_tile) * row_tile
    wo_pad = -(-wo // SUBLANE) * SUBLANE
    w_pad, c_pad = wo_pad + kw - 1, c
    if dma_aligned:
        w_pad = -(-w_pad // SUBLANE) * SUBLANE
        c_pad = -(-c // LANE) * LANE
    if (ho_pad, w_pad, c_pad) != (ho, w, c):
        x = jnp.pad(x, ((0, 0), (0, ho_pad - ho), (0, w_pad - w),
                        (0, c_pad - c)))
    return x, ho, wo, ho_pad, wo_pad


def tap_matmul(x_ref, w_ref, kh: int, kw: int, row0, rows: int, wo: int):
    """Sum over the ``kh * kw`` filter taps of the shifted ifmap window
    ``x_ref[row0+di : row0+di+rows, dj : dj+wo, :]`` (flattened to
    ``[rows*wo, C]``) times the tap's ``[C, Cout]`` filter slice, in f32
    on the MXU."""
    c = x_ref.shape[-1]
    acc = None
    for di in range(kh):
        for dj in range(kw):
            patch = x_ref[pl.ds(row0 + di, rows), pl.ds(dj, wo), :]
            part = jax.lax.dot(
                patch.reshape(rows * wo, c).astype(jnp.float32),
                w_ref[di, dj, :, :].astype(jnp.float32),
                precision=MATMUL_PRECISION,
                preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
    return acc


def accumulate_plane(x_ref, w_ref, acc_ref, kh: int, kw: int, wo: int):
    """``acc_ref [Ho*Wo, Cout] += conv(x_ref, w_ref)`` over the whole
    output plane, in bands of 8 output rows: a loop, not an unrolled
    plane-sized value, so compile time stays flat in the plane size."""
    band = SUBLANE * wo

    def body(r, carry):
        out0 = pl.multiple_of(r * band, band)
        acc_ref[pl.ds(out0, band), :] += tap_matmul(
            x_ref, w_ref, kh, kw, r * SUBLANE, SUBLANE, wo)
        return carry

    jax.lax.fori_loop(0, acc_ref.shape[0] // band, body, 0)
