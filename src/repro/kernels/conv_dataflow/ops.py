"""jit'd wrappers for the three conv-dataflow kernels.

``conv2d(x, w, dataflow=...)`` handles SAME/VALID padding and stride by
pre-padding / post-slicing around the stride-1 VALID kernels, and runs the
kernels interpreted on the CPU backend only.  Channel axes tile at 128
lanes (``tiling.LANE``) and the kernels zero-pad a channel count that 128
does not divide, so a layer of more than 128 input channels takes several
sequential steps of SconvOD's and MconvMC's channel grids, and one of more
than 128 output channels several MconvMC channel-pair tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat import pallas_interpret_default
from repro.kernels.conv_dataflow.mconv_mc import mconv_mc
from repro.kernels.conv_dataflow.ref import conv2d_ref
from repro.kernels.conv_dataflow.sconv_ic import sconv_ic
from repro.kernels.conv_dataflow.sconv_od import sconv_od

DATAFLOWS = ("SconvOD", "SconvIC", "MconvMC")


@functools.partial(jax.jit, static_argnames=("dataflow", "stride", "padding",
                                             "interpret"))
def conv2d(x: jax.Array, w: jax.Array, *, dataflow: str = "MconvMC",
           stride: int = 1, padding: str = "VALID",
           interpret: bool | None = None) -> jax.Array:
    """Conv2d through one of the paper's accelerator dataflows.

    x [N,H,W,Cin], w [KH,KW,Cin,Cout].
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    kh, kw = w.shape[:2]
    if padding == "SAME":
        ph, pw = (kh - 1) // 2, (kw - 1) // 2
        x = jnp.pad(x, ((0, 0), (ph, kh - 1 - ph), (pw, kw - 1 - pw), (0, 0)))

    if dataflow == "SconvOD":
        out = sconv_od(x, w, interpret=interpret)
    elif dataflow == "SconvIC":
        out = sconv_ic(x, w, interpret=interpret)
    elif dataflow == "MconvMC":
        out = mconv_mc(x, w, interpret=interpret)
    elif dataflow == "ref":
        out = conv2d_ref(x, w)
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    if stride > 1:
        out = out[:, ::stride, ::stride, :]
    return out
