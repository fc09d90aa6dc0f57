"""Word-exactness battery for the tiled trunk megakernel.

kernels/frame_trunk runs smallNet's entire conv->PLAN->pool trunk (with the
sweep's quad role maps) over a big frame in ONE Pallas launch.  Three
independent routes to the same int32 words get pinned pairwise:

  * the megakernel vs the untiled numpy int64 oracle (ref.py) on small
    random-word frames across tilings — interior, frame border, AND tile
    seams, in both Q16.16 and Q8.8;
  * the megakernel vs the composed per-stage FcnSweep cascade
    (`composed_trunk_quad`, the reference) on the real 112x112 streaming
    frame and on a 512x512 frame (where `choose_tile` splits 512x256 x2,
    so the seam path runs at acceptance scale) on both fixed substrates;
  * the end-to-end sweep scores (the backend's route vs the sweep head
    over the composed cascade) and the `conv_trunk` fast path vs the
    plain per-stage trunk.

Launch topology is asserted too: the megakernel trunk must trace to exactly
ONE `pallas_call`, the composed fixed_pallas cascade to many.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.analysis.launches import count_pallas_launches
from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import smallnet
from repro.kernels.fixed_conv.ref import random_words
from repro.kernels.frame_trunk import choose_tile, frame_trunk_quad
from repro.kernels.frame_trunk import ops as ft_ops
from repro.kernels.frame_trunk.ref import frame_trunk_quad_ref
from repro.streaming import fcn_sweep as fs
from repro.streaming.fcn_sweep import (FcnSweep, composed_feature_maps,
                                       sweep_feature_maps)
from repro.streaming.sources import SyntheticVideoSource

FIXED_BACKENDS = ("fixed", "fixed_pallas")
CFGS = {"q16_16": fxp.Q16_16, "q8_8": fxp.Q8_8}


@pytest.fixture(scope="module")
def params():
    return smallnet.seeded_params()


@pytest.fixture(scope="module")
def frame112():
    return SyntheticVideoSource(n_frames=1, seed=7).frames()[0]


def _rand_trunk_inputs(rng, shape, cfg):
    x = random_words(rng, shape, cfg)
    w1 = random_words(rng, (4,), cfg)
    b1 = random_words(rng, (1,), cfg)
    w2 = random_words(rng, (4,), cfg)
    b2 = random_words(rng, (1,), cfg)
    return x, w1, b1, w2, b2


def _composed_scores(params, frame, backend):
    """Window scores of `FcnSweep().score` with the trunk on the composed
    cascade: the sweep's own head over `composed_trunk_quad`."""
    sweep = FcnSweep()
    fb, _ = sweep.extract(frame)
    be = B.get_backend(backend)
    quad = jax.jit(lambda params, x: fs.composed_trunk_quad(
        be, be.prepare_params(params), x))(params, jnp.asarray(fb))
    head = fs.make_head_fn(backend, fb.shape[1:3], sweep.patch, sweep.stride)
    return np.asarray(head(params, quad))


def _assert_one_launch(params, pixels, backend):
    """The backend takes its one-launch `frame_trunk` for this frame
    (traced, not run), so a comparison with the composed cascade is not
    the cascade against itself."""
    be = B.get_backend(backend)
    p = be.prepare_params(params)
    frames = jnp.asarray(np.asarray(pixels, np.float32).reshape(
        1, *np.shape(pixels)[:2], 1))
    assert jax.eval_shape(lambda f: be.frame_trunk(f, p),
                          frames) is not None, f"{be.name}: no one-launch trunk"


def _assert_words(got, want, what):
    np.testing.assert_array_equal(
        np.asarray(got, np.int64), np.asarray(want, np.int64),
        err_msg=f"{what}: megakernel words drifted")


# ---------------------------------------------------------------------------
# megakernel vs the untiled numpy oracle, across tilings and formats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", sorted(CFGS))
@pytest.mark.parametrize("shape", [(8, 8), (16, 12), (24, 16)])
def test_megakernel_matches_oracle(fmt, shape):
    cfg = CFGS[fmt]
    rng = np.random.default_rng(hash((fmt, shape)) % 2**32)
    x, w1, b1, w2, b2 = _rand_trunk_inputs(rng, shape, cfg)
    want = frame_trunk_quad_ref(x, w1, b1, w2, b2, cfg)
    H, W = shape
    # one tile, the minimal 4x4 tiling (max seams), and a column split —
    # the oracle is untiled, so matching every tiling pins halo/DMA/seam
    # bookkeeping, not just the arithmetic
    for tile in (None, (H, W), (4, 4), (H, 4)):
        got = frame_trunk_quad(jnp.asarray(x, jnp.int32), w1, b1, w2, b2,
                               cfg=cfg, tile=tile)
        _assert_words(got, want, f"{fmt}/{shape}/tile={tile}")


def test_megakernel_tile_invariance():
    """Every legal tiling of the same frame produces identical words —
    seam columns/rows are indistinguishable from interior ones."""
    cfg = fxp.Q16_16
    rng = np.random.default_rng(11)
    x, w1, b1, w2, b2 = _rand_trunk_inputs(rng, (24, 24), cfg)
    outs = {}
    for tile in ((24, 24), (12, 12), (8, 8), (4, 4), (24, 8), (4, 24)):
        outs[tile] = np.asarray(frame_trunk_quad(
            jnp.asarray(x, jnp.int32), w1, b1, w2, b2, cfg=cfg, tile=tile))
    base = outs[(24, 24)]
    for tile, got in outs.items():
        _assert_words(got, base, f"tile={tile}")


def test_megakernel_rejects_bad_geometry():
    cfg = fxp.Q16_16
    x = jnp.zeros((16, 16), jnp.int32)
    w = jnp.ones((4,), jnp.int32)
    b = jnp.zeros((1,), jnp.int32)
    for shape in ((15, 16), (16, 18), (2, 16), (16, 2)):
        with pytest.raises(ValueError, match="frame"):
            frame_trunk_quad(jnp.zeros(shape, jnp.int32), w, b, w, b, cfg=cfg)
    for tile in ((5, 4), (4, 6), (12, 4), (4, 12), (2, 2)):
        with pytest.raises(ValueError, match="tile"):
            frame_trunk_quad(x, w, b, w, b, cfg=cfg, tile=tile)
    sat = fxp.FixedPointConfig(cfg.total_bits, cfg.frac_bits, saturate=True)
    with pytest.raises(NotImplementedError, match="wraparound"):
        frame_trunk_quad(x, w, b, w, b, cfg=sat)


# ---------------------------------------------------------------------------
# megakernel vs the composed FcnSweep cascade (the deployed pairing)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", FIXED_BACKENDS)
def test_sweep_maps_megakernel_vs_composed_112(params, frame112, backend):
    _assert_one_launch(params, frame112.pixels, backend)
    mega = sweep_feature_maps(params, frame112.pixels, backend=backend)
    comp = composed_feature_maps(params, frame112.pixels, backend=backend)
    for name in ("interior", "last_row", "last_col", "corner"):
        _assert_words(mega[name], comp[name], f"{backend}/112/{name}")


@pytest.mark.parametrize("backend", FIXED_BACKENDS)
def test_sweep_scores_megakernel_vs_composed_112(params, frame112, backend):
    _assert_one_launch(params, frame112.pixels, backend)
    fb, pos = FcnSweep().extract(frame112)
    got = FcnSweep().score(params, fb, backend=backend)
    want = _composed_scores(params, frame112, backend)
    _assert_words(got, want, f"{backend}/scores")


@pytest.mark.parametrize("backend", FIXED_BACKENDS)
def test_sweep_maps_megakernel_vs_composed_512(params, backend):
    """Acceptance-bar scale: choose_tile splits 512x512 into 512x256 x2, so
    the megakernel words cross a real tile seam (and the frame border)."""
    assert choose_tile(512, 512) != (512, 512)  # must genuinely tile
    rng = np.random.default_rng(512)
    frame = rng.random((512, 512), np.float32)
    _assert_one_launch(params, frame, backend)
    mega = sweep_feature_maps(params, frame, backend=backend)
    comp = composed_feature_maps(params, frame, backend=backend)
    for name in ("interior", "last_row", "last_col", "corner"):
        _assert_words(mega[name], comp[name], f"{backend}/512/{name}")


def test_sweep_megakernel_through_forced_small_tiles(params, frame112,
                                                     monkeypatch):
    """The backend-hook route with choose_tile forced to 28x28: sixteen
    tiles, fifteen seams, still word-identical end-to-end scores.  A
    fresh backend NAME dodges the `_sweep_fn` lru_cache (frozen-dataclass
    equality would otherwise reuse the unforced program)."""
    monkeypatch.setattr(ft_ops, "choose_tile", lambda H, W, **kw: (28, 28))
    be = B.FixedBackend(name="fixed_seamtest")
    _assert_one_launch(params, frame112.pixels, be)
    fb, pos = FcnSweep().extract(frame112)
    got = FcnSweep().score(params, fb, backend=be)
    want = _composed_scores(params, frame112, "fixed")
    _assert_words(got, want, "forced-28x28-tiles/scores")


# ---------------------------------------------------------------------------
# conv_trunk fast path + launch topology
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", FIXED_BACKENDS)
def test_conv_trunk_fast_path_matches_composed(params, frame112, backend):
    """smallnet.conv_trunk routes single big frames through the megakernel
    hook; its output must be word-identical to the composed per-stage
    trunk (the quad's interior map IS the plain trunk)."""
    x = frame112.pixels[None].astype(np.float32)   # pixels are (H, W, 1)
    got = smallnet.conv_trunk(params, x, backend=backend)
    be = B.get_backend(backend)
    want = smallnet._conv_stages(be, be.prepare_params(params), x)
    _assert_words(got, want, f"{backend}/conv_trunk")


def test_trunk_launch_topology(params, frame112):
    """ONE pallas_call per frame on the fixed substrates' route; the
    composed fixed_pallas cascade stays many; ref runs the composed
    cascade."""
    be = B.get_backend("fixed_pallas")
    p = be.prepare_params(params)
    frame = jnp.asarray(frame112.pixels[None], jnp.float32)
    n_mega = count_pallas_launches(
        lambda f: fs._trunk_quad(be, p, f), frame)
    n_comp = count_pallas_launches(
        lambda f: fs.composed_trunk_quad(be, p, f), frame)
    assert n_mega == 1, f"megakernel trunk traced {n_mega} pallas_calls"
    assert n_comp > 10, f"composed cascade traced only {n_comp}"
    # the emulated backend megakernel route is also exactly one launch
    bef = B.get_backend("fixed")
    pf = bef.prepare_params(params)
    assert count_pallas_launches(
        lambda f: fs._trunk_quad(bef, pf, f), frame) == 1
    # ref has no whole-frame trunk: its route is the composed cascade
    ber = B.get_backend("ref")
    pr = ber.prepare_params(params)
    assert jax.eval_shape(lambda f: ber.frame_trunk(f, pr), frame) is None
