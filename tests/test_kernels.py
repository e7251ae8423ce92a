"""Per-kernel correctness: shape/dtype sweeps vs pure-jnp oracles.

Execution mode follows ``repro.kernels.protocol``: interpret mode on the
CPU backend (the kernel body executes as XLA ops), compiled Mosaic on a
TPU — same tests, same tolerances, real tiles."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv_dataflow import conv2d, conv2d_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.protocol import compiled_available
from repro.kernels.ssd_scan import ssd_ref, ssd_scan

KEY = jax.random.PRNGKey(3)
INTERPRET = not compiled_available()

_TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
        jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


CONV_SHAPES = [
    (1, 8, 8, 4, 8, 3),
    (2, 12, 10, 8, 16, 5),
    (1, 6, 6, 3, 5, 1),
    (2, 16, 16, 16, 32, 3),
]


@pytest.mark.parametrize("dataflow", ["SconvOD", "SconvIC", "MconvMC"])
@pytest.mark.parametrize("shape", CONV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv_dataflow_vs_oracle(dataflow, shape, dtype):
    n, h, w_, ci, co, k = shape
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (n, h, w_, ci), jnp.float32)
    w = jax.random.normal(k2, (k, k, ci, co), jnp.float32) * 0.2
    ref = conv2d_ref(x, w)
    out = conv2d(x.astype(dtype), w.astype(dtype), dataflow=dataflow,
                 interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


def test_conv_same_padding_and_stride():
    x = jax.random.normal(KEY, (1, 9, 9, 4))
    w = jax.random.normal(KEY, (3, 3, 4, 8)) * 0.2
    out = conv2d(x, w, dataflow="MconvMC", padding="SAME", stride=2,
                 interpret=INTERPRET)
    assert out.shape == (1, 5, 5, 8)


def test_sconv_direct_calls_with_indivisible_tiles():
    """Tiles that don't divide the dim keep the REQUESTED tile: sconv_ic
    pads the output-row grid (masked tail band), sconv_od zero-pads the
    channel axis — neither degrades to a smaller divisor tile."""
    from repro.kernels.conv_dataflow.sconv_ic import sconv_ic
    from repro.kernels.conv_dataflow.sconv_od import sconv_od
    k1, k2 = jax.random.split(KEY)
    # ho = 9 with row_tile=8 and cin = 6 with cin_tile=4: the requested
    # tile does NOT divide the dim even after the min() clamp
    x = jax.random.normal(k1, (1, 11, 8, 6), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 6, 8), jnp.float32) * 0.2
    ref = conv2d_ref(x, w)
    out_ic = sconv_ic(x, w, row_tile=8, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out_ic), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    out_od = sconv_od(x, w, cin_tile=4, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out_od), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("ho", [13, 7, 23])
def test_sconv_prime_output_heights_keep_requested_tile(ho):
    """Prime output heights used to degrade the sconv_ic grid to
    row_tile=1 (one grid step per output row) and sconv_od to whatever
    divisor survived; both now pad to the requested tile and stay
    parity-exact."""
    from repro.kernels.conv_dataflow.sconv_ic import sconv_ic
    from repro.kernels.conv_dataflow.sconv_od import sconv_od
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, ho))
    x = jax.random.normal(k1, (2, ho + 2, 9, 11), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 11, 4), jnp.float32) * 0.2
    ref = conv2d_ref(x, w)
    out_ic = sconv_ic(x, w, row_tile=8, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out_ic), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # cin = 11 (prime) with cin_tile=8: zero-pads to 16, two grid steps
    out_od = sconv_od(x, w, cin_tile=8, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out_od), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_sconv_ic_tall_ifmap_halo_window():
    """H = 515: the old whole-ifmap-height BlockSpec would demand the
    full ifmap resident per grid step; the halo-window kernel streams
    bounded row_tile + kh - 1 windows and must stay parity-exact,
    including the padded tail band (ho = 513 = 64 * 8 + 1)."""
    from repro.kernels.conv_dataflow.sconv_ic import sconv_ic
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (1, 515, 8, 2), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 2, 4), jnp.float32) * 0.2
    out = sconv_ic(x, w, row_tile=8, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out), np.asarray(conv2d_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


def test_mconv_mc_pads_indivisible_channel_tiles():
    """cin = 11 and cout = 13 with 8-channel tiles: both channel axes
    zero-pad to 16, a 2 x 2 grid of channel-pair tiles, and the padded
    output channels are sliced off."""
    from repro.kernels.conv_dataflow.mconv_mc import mconv_mc
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (2, 10, 9, 11), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 11, 13), jnp.float32) * 0.2
    out = mconv_mc(x, w, cout_tile=8, cin_tile=8, interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out), np.asarray(conv2d_ref(x, w)),
                               rtol=1e-4, atol=1e-4)


def _pallas_grids(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                yield from _pallas_grids(getattr(inner, "jaxpr", inner))


@pytest.mark.parametrize("dataflow,grid", [
    ("SconvOD", (1, 2)),        # 2 sequential Cin steps: 204 -> 256 = 2 x 128
    ("SconvIC", (1, 4)),        # 4 output-row bands: 26 -> 32 = 4 x 8
    ("MconvMC", (1, 4, 2)),     # 4 Cout x 2 Cin tiles: 409 -> 512, 204 -> 256
])
def test_conv_dataflow_grids_at_a_real_layer(dataflow, grid):
    """A YOLO stage-512 3x3 conv at its spec width (204 -> 409 channels,
    26 x 26 on a 416-pixel input): each dataflow keeps its own grid at
    real widths — psums flow across sequential channel steps in SconvOD
    and MconvMC, and MconvMC tiles channel pairs."""
    from repro.models.perception.nets import YOLO_WIDTH
    cin, cout = int(256 * YOLO_WIDTH), int(512 * YOLO_WIDTH)
    x = jax.ShapeDtypeStruct((1, 26, 26, cin), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 3, cin, cout), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: conv2d(
        a, b, dataflow=dataflow, padding="SAME", interpret=INTERPRET))(x, w)
    assert list(_pallas_grids(jaxpr.jaxpr)) == [grid]


ATTN_SHAPES = [
    (1, 64, 4, 4, 32, True),
    (2, 128, 4, 2, 16, True),
    (1, 64, 2, 1, 32, False),   # MQA
    (2, 96, 8, 8, 64, True),
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_oracle(shape, dtype):
    b, s, h, kh, d, causal = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kh, d), jnp.float32)
    out = flash_attention(q.astype(dtype), k.astype(dtype), v.astype(dtype),
                          causal=causal, block_q=32, block_k=32,
                          interpret=INTERPRET)
    kr = jnp.repeat(k, h // kh, axis=2)
    vr = jnp.repeat(v, h // kh, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = kr.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = vr.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    ref = attention_ref(qf, kf, vf, causal=causal, scale=1 / math.sqrt(d))
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_TOL[dtype])


SSD_SHAPES = [
    (1, 32, 2, 8, 4, 8),
    (2, 64, 3, 16, 8, 16),
    (1, 48, 1, 8, 16, 16),
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_vs_oracle(shape, dtype):
    b, s, h, p, n, chunk = shape
    ks = jax.random.split(KEY, 4)
    u = (jax.random.normal(ks[0], (b, s, h, p), jnp.float32) * 0.3)
    a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.2
    Bm = jax.random.normal(ks[2], (b, s, n), jnp.float32) * 0.5
    Cm = jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.5
    y, sfin = ssd_scan(u.astype(dtype), a, Bm.astype(dtype),
                       Cm.astype(dtype), chunk=chunk, interpret=INTERPRET)
    uf = u.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    af = a.transpose(0, 2, 1).reshape(b * h, s)
    Bf = jnp.repeat(Bm[:, None], h, 1).reshape(b * h, s, n)
    Cf = jnp.repeat(Cm[:, None], h, 1).reshape(b * h, s, n)
    yr, hr = ssd_ref(uf, af, Bf, Cf)
    yr = yr.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    hr = hr.reshape(b, h, n, p)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **_TOL[dtype])
    np.testing.assert_allclose(np.asarray(sfin, np.float32),
                               np.asarray(hr, np.float32), **_TOL[dtype])
