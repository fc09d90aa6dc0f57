"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d import conv2d, conv2d_ref
from repro.kernels.maxpool2d import maxpool2d, maxpool2d_ref
from repro.kernels.quant_matmul import quant_matmul, quant_matmul_ref
from repro.kernels.sigmoid_pla import sigmoid_pla, sigmoid_pla_ref


@pytest.mark.parametrize("B,H,W,ci,co,kh,kw,pad,sig,stride", [
    (2, 28, 28, 1, 1, 2, 2, "SAME", True, 1),     # smallNet conv1
    (2, 14, 14, 1, 1, 2, 2, "SAME", True, 1),     # smallNet conv2
    (1, 16, 16, 3, 8, 3, 3, "SAME", False, 1),
    (3, 16, 12, 4, 4, 2, 2, "VALID", False, 1),
    (1, 32, 32, 2, 6, 5, 5, "SAME", False, 2),
    (2, 8, 8, 8, 16, 1, 1, "VALID", False, 1),
])
def test_conv2d_vs_ref(B, H, W, ci, co, kh, kw, pad, sig, stride, rng):
    x = jnp.asarray(rng.normal(size=(B, H, W, ci)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(kh, kw, ci, co)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(co,)), jnp.float32)
    got = conv2d(x, w, b, padding=pad, apply_sigmoid=sig, stride=stride)
    want = conv2d_ref(x, w, b, padding=pad, apply_sigmoid=sig, stride=stride)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("M,K,N", [
    (64, 49, 10),        # smallNet dense
    (256, 512, 256),     # aligned
    (100, 300, 70),      # unaligned -> wrapper pads
    (8, 128, 8),
    (513, 257, 129),
])
def test_quant_matmul_vs_ref(M, K, N, rng):
    xq = jnp.asarray(rng.integers(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    sx = jnp.asarray(rng.uniform(0.01, 0.1, (M,)), jnp.float32)
    sw = jnp.asarray(rng.uniform(0.01, 0.1, (N,)), jnp.float32)
    got = quant_matmul(xq, wq, sx, sw)
    want = quant_matmul_ref(xq, wq, sx, sw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_quant_matmul_int32_exactness(rng):
    # accumulation must be exact int32 (no float roundoff): compare against
    # numpy int64 accumulation
    xq = rng.integers(-127, 128, (32, 1024)).astype(np.int8)
    wq = rng.integers(-127, 128, (1024, 16)).astype(np.int8)
    got = np.asarray(quant_matmul(jnp.asarray(xq), jnp.asarray(wq), 1.0, 1.0))
    want = (xq.astype(np.int64) @ wq.astype(np.int64)).astype(np.float64)
    np.testing.assert_array_equal(got.astype(np.int64), want.astype(np.int64))


@pytest.mark.parametrize("shape", [(7,), (33, 5), (2, 3, 4, 5), (1000,), (256, 128)])
@pytest.mark.parametrize("scale", [0.1, 4.0, 20.0])
def test_sigmoid_pla_vs_ref(shape, scale, rng):
    x = jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    np.testing.assert_allclose(np.asarray(sigmoid_pla(x)),
                               np.asarray(sigmoid_pla_ref(x)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("B,H,W,C", [(2, 28, 28, 1), (1, 14, 14, 1),
                                     (2, 15, 9, 2), (3, 8, 8, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxpool2d_vs_ref(B, H, W, C, dtype, rng):
    x = jnp.asarray(rng.normal(size=(B, H, W, C)), dtype)
    got = maxpool2d(x)
    want = maxpool2d_ref(x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


def test_conv2d_vmem_guard():
    x = jnp.zeros((1, 1024, 1024, 8), jnp.float32)
    w = jnp.zeros((3, 3, 8, 8), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        conv2d(x, w)


@pytest.mark.parametrize("activation", [None, "sigmoid", "plan"])
def test_conv2d_fused_activation_epilogue(activation, rng):
    # smallNet conv1 shape with each fused epilogue vs the composed oracle
    x = jnp.asarray(rng.normal(size=(2, 28, 28, 1)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 2, 1, 1)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1,)), jnp.float32)
    got = conv2d(x, w, b, activation=activation)
    want = conv2d_ref(x, w, b, activation=activation)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_conv2d_bad_activation_rejected():
    x = jnp.zeros((1, 8, 8, 1), jnp.float32)
    w = jnp.zeros((2, 2, 1, 1), jnp.float32)
    with pytest.raises(ValueError, match="activation"):
        conv2d(x, w, activation="relu")


def test_conv2d_stride2_vmem_budgets_strided_output():
    """Strides are realized NATIVELY (only kept rows/columns are MAC'd), so
    the VMEM budget covers just the strided output: a shape whose stride-1
    output would blow the budget fits comfortably at stride 2."""
    x = jnp.zeros((1, 512, 512, 1), jnp.float32)
    w = jnp.zeros((2, 2, 1, 16), jnp.float32)
    # stride-1 output 512*512*16*4 B ~= 16.8 MB > 14 MB budget...
    with pytest.raises(ValueError, match="strided output"):
        conv2d(x, w, stride=1)
    # ...but the stride-2 output is only ~4.2 MB, so the SAME image now runs
    y = conv2d(x, w, stride=2)
    assert y.shape == (1, 256, 256, 16)


def test_conv2d_stride2_large_frame_matches_ref(rng):
    """The natively-strided kernel on a streaming-tiler-sized frame agrees
    with the decimate-a-stride-1-output oracle."""
    x = jnp.asarray(rng.normal(size=(1, 112, 112, 1)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 2, 1, 4)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
    for stride in (2, 3, 4):
        got = conv2d(x, w, b, stride=stride)
        want = conv2d_ref(x, w, b, stride=stride)
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_conv2d_stride2_small_shape_still_exact(rng):
    x = jnp.asarray(rng.normal(size=(2, 12, 10, 3)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 2, 3, 4)), jnp.float32)
    got = conv2d(x, w, stride=2)
    want = conv2d_ref(x, w, stride=2)
    assert got.shape == (2, 6, 5, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# frame-extent generalization (the FCN sweep runs whole frames, not 28x28)
# ---------------------------------------------------------------------------

def test_conv2d_frame_extent_fused_stage(rng):
    """The smallNet conv stage (2x2 SAME + fused sigmoid) at streaming
    frame size — the sweep's per-frame launch shape — matches the oracle
    and fits the VMEM budget with room to spare."""
    x = jnp.asarray(rng.normal(size=(1, 112, 112, 1)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 2, 1, 1)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1,)), jnp.float32)
    got = conv2d(x, w, b, activation="sigmoid")
    want = conv2d_ref(x, w, b, activation="sigmoid")
    assert got.shape == (1, 112, 112, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fixed_conv_frame_extent_and_budget(rng):
    """kernels/fixed_conv at frame extents: the fused conv+PLAN+pool launch
    on a 112x112 word map matches the emulated backend word-for-word, odd
    extents pool like the emulated path, and the budget check trips on
    frames that genuinely exceed VMEM (with the limb temporaries counted)."""
    from repro.core import backends as B
    from repro.core import fixed_point as fxp
    from repro.kernels.fixed_conv import fixed_conv2d

    cfg = fxp.Q16_16
    x = jnp.asarray(rng.integers(-2 ** 20, 2 ** 20, (1, 112, 112)), jnp.int32)
    w4 = jnp.asarray(rng.integers(-2 ** 14, 2 ** 14, (4,)), jnp.int32)
    b = jnp.int32(rng.integers(-2 ** 14, 2 ** 14))
    got = fixed_conv2d(x, w4, b, cfg=cfg, activation="plan", pool=True)
    want = B.maxpool_fixed(fxp.fixed_sigmoid_plan(
        B.conv_fixed(x, w4, b, cfg), cfg))
    assert got.shape == (1, 56, 56)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # odd extent: even-crop before pooling, exactly like maxpool_fixed
    odd = fixed_conv2d(x[:, :29, :29], w4, b, cfg=cfg, activation="plan",
                       pool=True)
    assert odd.shape == (1, 14, 14)
    # a frame past ~670x670 exceeds input + limb-temporary VMEM
    with pytest.raises(ValueError, match="VMEM"):
        fixed_conv2d(jnp.zeros((1, 700, 700), jnp.int32), w4, b, cfg=cfg)


@pytest.mark.parametrize("H,W", [(28, 28), (14, 14), (114, 58), (7, 9)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_pooling_selects_match_strided_slices(H, W, dtype, rng):
    """The Mosaic-lowerable pools (kernels/pooling.py) are exactly the
    strided-slice comparator trees they replace."""
    from repro.kernels.pooling import pool2x2, pool_mix, pool_quadrants
    maps = [np.asarray(rng.integers(-2**31, 2**31 - 1, (H, W)), np.int64)
            .astype(np.dtype(dtype)) for _ in range(4)]
    y = maps[0][:H - H % 2, :W - W % 2]
    np.testing.assert_array_equal(
        np.asarray(pool2x2(jnp.asarray(maps[0]))),
        np.maximum(np.maximum(y[::2, ::2], y[::2, 1::2]),
                   np.maximum(y[1::2, ::2], y[1::2, 1::2])))
    if H % 2 or W % 2:
        return
    tl, tr, bl, br = maps
    want = np.maximum(np.maximum(tl[::2, ::2], tr[::2, 1::2]),
                      np.maximum(bl[1::2, ::2], br[1::2, 1::2]))
    got = pool_quadrants(*(jnp.asarray(m) for m in maps))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(pool_mix(jnp.asarray(tl), jnp.asarray(bl))),
        np.maximum(np.maximum(tl[::2, ::2], tl[::2, 1::2]),
                   np.maximum(bl[1::2, ::2], bl[1::2, 1::2])))
