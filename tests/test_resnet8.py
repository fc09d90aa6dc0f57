"""ResNet-8 (core/resnet8.py) on the ref, fixed and fixed_pallas backends,
its multi-channel conv kernel (kernels/fixed_conv_mc) and `VisionEngine`
serving it — on the CPU, at small sizes, Pallas in interpret mode.

Word-level checks compare with the benchmark's plain numpy reference
(`chipbench/reference_resnet8.py`, which imports nothing of the program)
and with the kernel's direct-loop oracle (`fixed_conv_mc/ref.py`).
"""
from __future__ import annotations

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import gen_resnet8  # noqa: E402
from chipbench import reference_resnet8 as R8  # noqa: E402
from chipbench.reference import Fmt  # noqa: E402
from repro.configs.resnet8 import RESNET8  # noqa: E402
from repro.core import backends as B  # noqa: E402
from repro.core import fixed_point as fxp  # noqa: E402
from repro.core import resnet8, smallnet  # noqa: E402
from repro.kernels.fixed_conv.ref import random_words  # noqa: E402
from repro.kernels.fixed_conv_mc import (fixed_conv_mc,  # noqa: E402
                                         fixed_conv_mc_ref)
from repro.kernels.fixed_conv_mc import ops as mc_ops  # noqa: E402
from repro.serving.router import ReplicaRouter  # noqa: E402
from repro.serving.vision_engine import VisionEngine  # noqa: E402

SEED = 2**31 + 4099
# ResNet-8's graph at narrower channels (4 / 8 / 8) on 16x16 images
NARROW = {"stem": (3, 3, 4, 1), "s1a": (3, 4, 4, 1), "s1b": (3, 4, 4, 1),
          "s2a": (3, 4, 8, 2), "s2b": (3, 8, 8, 1), "s2p": (1, 4, 8, 2),
          "s3a": (3, 8, 8, 2), "s3b": (3, 8, 8, 1), "s3p": (1, 8, 8, 2)}
SIDE = 16
FORMATS = {"q16_16": fxp.Q16_16, "q8_8": fxp.Q8_8}
# Q16.16 logits against the float32 reference, in real units. One Q16.16
# product rounds to within 2**-17, so the widest MAC (576 products) is off
# by at most 576 * 2**-17 = 0.0044 in the worst case; rounding errors of
# either sign add like a random walk over the nine conv layers, and the
# folded BN keeps each block's gain near 1, so the logits stay within
# 2**-7. Q8.8 rounds every product to 2**-9, 256 times coarser: its error
# exceeds 2**-7 on every image.
LOGIT_TOL = 2.0 ** -7


def _fmt(cfg: fxp.FixedPointConfig) -> Fmt:
    return Fmt(cfg.total_bits, cfg.frac_bits, cfg.round_nearest)


@pytest.fixture(scope="module")
def narrow():
    return (gen_resnet8.params(SEED, NARROW),
            gen_resnet8.images(SEED, 2, SIDE))


@pytest.fixture(scope="module")
def published():
    """Published widths: params, two images, the float reference's logits
    and one image's Q16.16 words from the emulated fixed backend."""
    p = gen_resnet8.params(SEED)
    imgs = gen_resnet8.images(SEED, 2)
    ref = np.asarray(resnet8.forward_ref(p, jnp.asarray(imgs)))
    words = np.asarray(resnet8.apply(p, jnp.asarray(imgs[:1]),
                                     backend="fixed"))
    return p, imgs, ref, words


# -- the published shapes ---------------------------------------------------

def test_published_shapes_and_counts():
    p = resnet8.init_params(jax.random.key(0))
    assert resnet8.param_count(p) == RESNET8["params"] == 77706
    assert resnet8.param_count(gen_resnet8.params(SEED)) == 77706
    macs = sum(k * k * cin * cout * (-(-side // s)) ** 2
               for (k, cin, cout, s), side in zip(
                   resnet8.CONVS.values(),
                   (32, 32, 32, 32, 16, 32, 16, 8, 16)))
    assert macs + 64 * 10 == RESNET8["macs_per_image"] == 12501632
    x = jnp.zeros((2,) + resnet8.IMAGE_SHAPE, jnp.float32)
    assert resnet8.apply(p, x).shape == (2, 10)


@pytest.mark.parametrize("size,k,stride,want", [
    (32, 3, 1, (32, 1, 1)), (32, 3, 2, (16, 0, 1)), (32, 1, 2, (16, 0, 0)),
    (16, 3, 2, (8, 0, 1)), (7, 3, 2, (4, 1, 1))])
def test_same_padding_is_tensorflow_same(size, k, stride, want):
    assert mc_ops.same_padding(size, k, stride) == want


# -- backends against the references ----------------------------------------

def test_ref_backend_matches_forward_ref(published):
    p, imgs, ref, _ = published
    got = np.asarray(resnet8.apply(p, jnp.asarray(imgs), backend="ref"))
    # the same float32 sums in another order (lax.conv against per-tap
    # contractions), both at full f32 precision
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["fixed", "fixed_pallas"])
@pytest.mark.parametrize("fmt", list(FORMATS), ids=list(FORMATS))
def test_fixed_backends_match_reference(narrow, backend, fmt):
    p, imgs = narrow
    cfg = FORMATS[fmt]
    be = (B.FixedBackend(cfg=cfg) if backend == "fixed"
          else B.FixedPallasBackend(cfg=cfg))
    got = np.asarray(resnet8.apply(p, jnp.asarray(imgs), backend=be))
    np.testing.assert_array_equal(got, R8.score_images(imgs, p, _fmt(cfg)))


def test_wrapping_words_match_reference(narrow):
    """Weights 256 times too large drive every stage past the word's
    range: the sums wrap, and the program still gives the reference's
    words."""
    p, imgs = narrow
    big = jax.tree_util.tree_map(lambda a: a * np.float32(256.0), p)
    float_logits = np.asarray(resnet8.forward_ref(big, jnp.asarray(imgs)))
    assert np.abs(float_logits).max() > 2.0 ** 15      # beyond Q16.16
    want = R8.score_images(imgs, big, _fmt(fxp.Q16_16))
    for be in ("fixed", "fixed_pallas"):
        got = np.asarray(resnet8.apply(big, jnp.asarray(imgs), backend=be))
        np.testing.assert_array_equal(got, want)


def test_fixed_published_widths_match_reference(published):
    p, imgs, _, words = published
    np.testing.assert_array_equal(
        words, R8.score_images(imgs[:1], p, _fmt(fxp.Q16_16)))


def test_fixed_within_tolerance_of_float_and_q8_is_not(published):
    p, imgs, ref, words = published
    err16 = np.abs(words / 2.0 ** 16 - ref[:1]).max()
    assert err16 <= LOGIT_TOL
    q8 = R8.score_images(imgs, p, _fmt(fxp.Q8_8)) / 2.0 ** 8
    assert np.all(np.abs(q8 - ref).max(axis=1) > LOGIT_TOL)


# -- the multi-channel conv kernel -----------------------------------------

@pytest.mark.parametrize("cfg", list(fxp.STANDARD_CONFIGS.values()),
                         ids=list(fxp.STANDARD_CONFIGS))
@pytest.mark.parametrize("k,stride", [(3, 1), (3, 2), (1, 2)])
def test_conv_kernel_matches_oracle_and_emulation(cfg, k, stride, rng):
    x = random_words(rng, (2, 8, 8, 5), cfg)
    w = random_words(rng, (k, k, 5, 6), cfg)
    b = random_words(rng, (6,), cfg)
    xi, wi, bi = (jnp.asarray(a, jnp.int32) for a in (x, w, b))
    got = np.asarray(fixed_conv_mc(xi, wi, bi, stride=stride, cfg=cfg))
    np.testing.assert_array_equal(
        got, fixed_conv_mc_ref(x, w, b, cfg, stride=stride))
    np.testing.assert_array_equal(
        got, np.asarray(B.conv_fixed_mc(xi, wi, bi, stride, cfg)))


@pytest.mark.parametrize("cfg", [fxp.Q16_16, fxp.Q8_8], ids=["q16", "q8"])
@pytest.mark.parametrize("bk", [1, 4, 7, 9])
def test_k_tiled_conv_equals_one_block(cfg, bk, rng):
    """A reduction tiled over K (zero-padded where bk does not divide it)
    gives the words of one K block, on sums that wrap: the operands hold
    the word's extremes, so the sums pass the word's range."""
    x = random_words(rng, (1, 6, 6, 4), cfg, extremes=12)
    w = random_words(rng, (3, 3, 4, 3), cfg, extremes=6)
    b = random_words(rng, (3,), cfg)
    xi, wi, bi = (jnp.asarray(a, jnp.int32) for a in (x, w, b))
    one = np.asarray(fixed_conv_mc(xi, wi, bi, cfg=cfg, blocks=(36, 8)))
    tiled = np.asarray(fixed_conv_mc(xi, wi, bi, cfg=cfg, blocks=(bk, 8)))
    np.testing.assert_array_equal(tiled, one)
    # the reduction does wrap: the unwrapped sum leaves the word's range
    cols = np.asarray(mc_ops.im2col(xi, 3, 3, 1)[0], np.float64)
    exact = np.abs(cols.T @ w.reshape(36, 3).astype(np.float64)).max()
    assert exact / cfg.scale > 2.0 ** (cfg.total_bits - 1)


@pytest.mark.parametrize("name", list(resnet8.CONVS))
def test_blocks_fit_the_budgets(name):
    k, cin, cout, stride = resnet8.CONVS[name]
    side = {"stem": 32, "s1a": 32, "s1b": 32, "s2a": 32, "s2b": 16,
            "s2p": 32, "s3a": 16, "s3b": 8, "s3p": 16}[name]
    M = 32 * (-(-side // stride)) ** 2
    K = k * k * cin
    bk, bs = mc_ops.choose_blocks(K, M, cout)
    assert bk * cout <= mc_ops._SMEM_WORDS
    assert bk == K or (bk * cout) % mc_ops._SMEM_TILE == 0
    assert K % bk == 0                      # no padded reduction rows
    assert bs % 8 == 0 and bs * (M // 128 // bs) * 128 == M
    assert mc_ops.vmem_bytes(bk, bs, cout) <= mc_ops._VMEM_BUDGET


def test_global_avgpool_is_a_rounded_shift():
    cfg = fxp.Q16_16
    x = np.zeros((2, 8, 8, 3), np.int64)
    x[0, 0, 0, 0] = 32                      # 32 / 64 = 0.5 -> rounds up
    x[0, 0, 0, 1] = 31                      # 0.48 -> 0
    x[0, 0, 0, 2] = -33                     # -0.52 -> -1
    x[1] = 2 ** 30                          # the int32 sum wraps
    got = np.asarray(B.global_avgpool_fixed(jnp.asarray(x, jnp.int32), cfg))
    np.testing.assert_array_equal(got[0], [1, 0, -1])
    np.testing.assert_array_equal(got, R8.avgpool_shift(x, _fmt(cfg)))
    with pytest.raises(ValueError):
        B.global_avgpool_fixed(jnp.zeros((1, 6, 6, 1), jnp.int32), cfg)


# -- served through the engine and the router -------------------------------

def test_engine_serves_resnet8_word_exact(narrow):
    p, _ = narrow
    imgs = gen_resnet8.images(SEED + 1, 6, SIDE)
    eng = VisionEngine(p, backend="fixed_pallas", model=resnet8,
                       image_shape=(SIDE, SIDE, 3), batch_size=4)
    res = eng.serve(list(imgs))
    want = R8.score_images(imgs, p, _fmt(fxp.Q16_16))
    np.testing.assert_array_equal(np.stack([r.scores for r in res]), want)
    assert [r.pred for r in res] == list(want.argmax(axis=1))


def test_engine_defaults_to_smallnet_unchanged():
    p = smallnet.seeded_params(3)
    imgs = np.random.default_rng(5).uniform(0, 1, (5, 28, 28, 1)).astype(
        np.float32)
    eng = VisionEngine(p, backend="fixed", batch_size=8)
    assert eng.model is smallnet and eng.image_shape == (28, 28, 1)
    res = eng.serve(list(imgs))
    want = np.asarray(smallnet.apply(p, jnp.asarray(imgs), backend="fixed"))
    np.testing.assert_array_equal(np.stack([r.scores for r in res]), want)


def test_router_passes_the_model_through(narrow):
    p, imgs = narrow
    router = ReplicaRouter.from_backends(
        p, ["fixed"], batch_size=2,
        engine_kw={"model": resnet8, "image_shape": (SIDE, SIDE, 3)})
    assert all(r.model is resnet8 for r in router.replicas)
    res = router.serve(list(imgs))
    np.testing.assert_array_equal(
        np.stack([r.scores for r in res]),
        R8.score_images(imgs, p, _fmt(fxp.Q16_16)))
