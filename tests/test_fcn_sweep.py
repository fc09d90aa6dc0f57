"""FCN frame sweep: the sweep-vs-tiler equivalence battery.

The contract under test (streaming/fcn_sweep.py): scoring a 28x28 window
from the full-frame sweep trunk is EQUAL to `Tiler.extract`+`score` on the
host-extracted patch — word-exact int32 for the fixed substrates (interior
AND border windows, thanks to the masked-weight edge maps), float-tight
(~1 ulp of XLA conv accumulation order) for the float backends — and
therefore frozen-clip detections are identical between the two paths, both
offline and through the streaming pipeline.  The geometry/edge contract
(positions on the stride-4 pooled lattice, wraparound-only fixed configs)
must fail loudly, never approximately.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import runtime
from repro.core import smallnet
from repro.obs import metrics as M
from repro.serving.vision_engine import VisionEngine
from repro.streaming import fcn_sweep as fs
from repro.streaming.fcn_sweep import FcnSweep, sweep_feature_maps
from repro.streaming.pipeline import StreamingPipeline
from repro.streaming.sources import SyntheticVideoSource
from repro.streaming.tiler import Tiler, tile_positions

FIXED_BACKENDS = ("fixed", "fixed_pallas")
PARITY_BACKENDS = ("ref", "fixed", "fixed_pallas")


@pytest.fixture(scope="module")
def params():
    return smallnet.seeded_params()


@pytest.fixture(scope="module")
def clip():
    return SyntheticVideoSource(n_frames=3, seed=7)


@pytest.fixture(scope="module")
def frame112(clip):
    return clip.frames()[0]


@pytest.fixture(scope="module")
def small_frame():
    """36x36: 3x3 = 9 windows at stride 4 — cheap enough for the Pallas
    interpreter backends."""
    rng = np.random.default_rng(5)
    return rng.random((36, 36, 1)).astype(np.float32)


@pytest.fixture(scope="module")
def calibrated(params, frame112):
    """Shared (tiler, sweep) pair at stride 8 with the threshold pinned to
    the 80th pct of first-frame 'fixed' confidences (deterministic nonzero
    detections on the frozen clip)."""
    t0 = Tiler(stride=8)
    tiles, _ = t0.extract(frame112)
    conf = t0._confidences(t0.score(params, tiles, backend="fixed")).max(-1)
    thr = float(np.quantile(conf, 0.8))
    return Tiler(stride=8, threshold=thr), FcnSweep(stride=8, threshold=thr)


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

def test_sweep_positions_match_tiler_lattice():
    for stride in (4, 8, 12, 28):
        assert FcnSweep(stride=stride).positions((112, 112)) == \
            tile_positions((112, 112), 28, stride)


def _geometry_builds() -> int:
    return M.REGISTRY.counter(M.SWEEP_LATTICE_BUILDS).value


def _noise_frame(shape):
    return np.random.default_rng(shape[1]).random(shape + (1,)).astype(
        np.float32)


@pytest.mark.parametrize("shape", ((112, 112), (120, 160)))
def test_geometry_built_once_per_frame_shape(params, monkeypatch, shape):
    """Two extract + score calls on frames of one shape build the window
    geometry once, and the second builds no position list at all; a
    second shape builds its own."""
    fs._sweep_geometry.cache_clear()
    sweep = FcnSweep(stride=8)
    before = _geometry_builds()
    fb, pos = sweep.extract(_noise_frame(shape))
    first = sweep.score(params, fb, backend="fixed")
    assert _geometry_builds() == before + 1

    def rebuilt(*args):
        raise AssertionError("window positions rebuilt for a known shape")

    with monkeypatch.context() as m:
        m.setattr(fs, "tile_positions", rebuilt)
        fb2, pos2 = sweep.extract(_noise_frame(shape))
        second = sweep.score(params, fb2, backend="fixed")
    assert pos2 is pos
    assert second.shape == first.shape == (len(pos), 10)
    assert _geometry_builds() == before + 1
    sweep.extract(_noise_frame((shape[0] + 8, shape[1])))
    assert _geometry_builds() == before + 2


@pytest.mark.parametrize("stride", (4, 8, 12))
@pytest.mark.parametrize("shape", ((112, 112), (120, 160)))
def test_stored_positions_equal_tile_positions(shape, stride):
    """The stored positions are `tile_positions`' list, the same object
    for every caller of the shape, and the lattice reads them."""
    geometry = fs._sweep_geometry(*shape, 28, stride)
    want = tile_positions(shape, 28, stride)
    assert geometry.positions == want
    assert FcnSweep(stride=stride).positions(shape) is geometry.positions
    assert geometry.lattice == fs._window_lattice(28, tuple(want))


def test_reset_hooks_rebuild_geometry_with_the_same_words(params):
    """The runtime's reset hooks clear the geometry cache with the
    program caches; the rebuilt program scores the same words."""
    assert fs._sweep_geometry.cache_clear in runtime._RESET_HOOKS
    sweep = FcnSweep(stride=8)
    fb, _ = sweep.extract(_noise_frame((120, 160)))
    before = sweep.score(params, fb, backend="fixed")
    builds = _geometry_builds()
    for hook in runtime._RESET_HOOKS:
        hook()
    after = sweep.score(params, fb, backend="fixed")
    assert _geometry_builds() == builds + 1
    np.testing.assert_array_equal(after, before)


def test_edge_contract_fails_loudly():
    with pytest.raises(ValueError, match="multiple of 4"):
        FcnSweep(stride=14)                       # off-lattice stride
    with pytest.raises(ValueError, match="multiple of 4"):
        FcnSweep(patch=30)                        # off-lattice patch
    with pytest.raises(ValueError, match="edge contract"):
        FcnSweep(stride=8).positions((110, 112))  # clamped window off-lattice
    with pytest.raises(ValueError, match="one frame per call"):
        FcnSweep().score({}, np.zeros((2, 112, 112, 1), np.float32))


def test_saturating_config_rejected(params, small_frame):
    sat = B.FixedBackend(cfg=fxp.FixedPointConfig(32, 16, saturate=True))
    with pytest.raises(NotImplementedError, match="wraparound"):
        FcnSweep(stride=4).score(params, small_frame[None], backend=sat)


# ---------------------------------------------------------------------------
# per-window score equality vs the host tiler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", sorted(B.list_backends()))
def test_per_window_scores_match_tiler_every_backend(params, small_frame,
                                                     backend):
    """Every registered backend: sweep score == patch score per window —
    exact int32 words for integer-scored backends, allclose (the float
    convs' accumulation-order latitude) for float ones."""
    t, s = Tiler(stride=4), FcnSweep(stride=4)
    tiles, pos_t = t.extract(small_frame)
    fb, pos_s = s.extract(small_frame)
    assert pos_t == pos_s
    want = t.score(params, tiles, backend=backend)
    got = s.score(params, fb, backend=backend)
    assert got.shape == want.shape == (len(pos_t), 10)
    if np.issubdtype(want.dtype, np.integer):
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("backend", FIXED_BACKENDS)
def test_full_frame_word_exact_including_border_windows(params, frame112,
                                                        backend):
    """112x112 at stride 4 (484 windows): every window's int32 score words
    — interior AND the edge-clamped border rows/cols — equal the host
    tiler's, which is the acceptance bar for detection parity."""
    t, s = Tiler(stride=4), FcnSweep(stride=4)
    tiles, pos = t.extract(frame112)
    want = t.score(params, tiles, backend=backend)
    got = s.score(params, s.extract(frame112)[0], backend=backend)
    np.testing.assert_array_equal(got, want)
    border = [i for i, (y, x) in enumerate(pos) if y == 84 or x == 84]
    assert border, "the clamped border windows must be part of the sweep"
    np.testing.assert_array_equal(got[border], want[border])


def test_fixed_vs_fixed_pallas_bitexact_through_sweep_trunk(params, frame112):
    """The two fixed substrates must agree word-for-word on all four
    role maps of the sweep trunk AND on the final window scores."""
    maps = {b: sweep_feature_maps(params, frame112.pixels, backend=b)
            for b in FIXED_BACKENDS}
    for name in ("interior", "last_row", "last_col", "corner"):
        a, b = maps["fixed"][name], maps["fixed_pallas"][name]
        assert a.dtype == b.dtype == np.int32
        assert a.shape == b.shape == (28, 28)
        np.testing.assert_array_equal(a, b, err_msg=f"map {name!r} drifted")
    s = FcnSweep(stride=4)
    fb, _ = s.extract(frame112)
    np.testing.assert_array_equal(
        s.score(params, fb, backend="fixed"),
        s.score(params, fb, backend="fixed_pallas"))


# ---------------------------------------------------------------------------
# detections: offline parity + the pipeline path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", PARITY_BACKENDS)
def test_frozen_clip_detection_parity(params, clip, calibrated, backend):
    """Detections identical sweep-vs-tiler: strictly (float scores
    included) on the word-exact fixed substrates; labels/positions exact
    with 1e-5-tolerant scores on 'ref', whose conv summation order has
    ~1-ulp latitude between the two paths."""
    tiler, sweep = calibrated
    dt = [tiler.detect(params, f, backend=backend) for f in clip.frames()]
    ds = [sweep.detect(params, f, backend=backend) for f in clip.frames()]
    assert sum(len(d) for d in dt) > 0
    if backend in FIXED_BACKENDS:
        assert dt == ds
    else:
        for a, b in zip(dt, ds):
            assert [(d.label, d.y, d.x, d.size) for d in a] == \
                [(d.label, d.y, d.x, d.size) for d in b]
            np.testing.assert_allclose([d.score for d in a],
                                       [d.score for d in b], atol=1e-5)


def test_min_mass_gate_matches_tiler(params, frame112, calibrated):
    thr = calibrated[0].threshold
    t = Tiler(stride=8, threshold=thr, min_mass=0.04)
    s = FcnSweep(stride=8, threshold=thr, min_mass=0.04)
    dt = t.detect(params, frame112, backend="fixed")
    ds = s.detect(params, frame112, backend="fixed")
    assert dt == ds
    # the gate actually bit: fewer (or equal) detections than ungated
    assert len(ds) <= len(calibrated[1].detect(params, frame112,
                                               backend="fixed"))


def test_confidence_grid_matches_tiler_on_sweep_lattice(params, frame112,
                                                        calibrated):
    tiler, sweep = calibrated
    tiles, pos = tiler.extract(frame112)
    fb, _ = sweep.extract(frame112)
    gt = tiler.confidence_grid(tiler.score(params, tiles, backend="fixed"), pos)
    gs = sweep.confidence_grid(sweep.score(params, fb, backend="fixed"), pos)
    assert gt.shape == gs.shape == (12, 12)      # range(0,84,8)+[84] per axis
    np.testing.assert_array_equal(gs, gt)


def test_pipeline_sweep_serves_offline_sweep_detections(params, clip,
                                                        calibrated):
    _, sweep = calibrated
    eng = VisionEngine(params, backend="fixed", batch_size=64, warmup=False)
    pipe = StreamingPipeline(clip, eng, sweep)
    res = pipe.run()
    s = pipe.stats()
    assert s["accounted"] and s["frames_served"] == len(clip)
    offline = [sweep.detect(params, f, backend="fixed") for f in clip.frames()]
    assert [r.detections for r in res] == offline
    assert s["detections_total"] == sum(len(d) for d in offline) > 0


def test_pipeline_sweep_rejects_engines_without_model(calibrated):
    class NoModel:
        def serve(self, tiles):
            return []
    with pytest.raises(TypeError, match="params/backend"):
        StreamingPipeline(SyntheticVideoSource(n_frames=1), NoModel(),
                          calibrated[1])


# ---------------------------------------------------------------------------
# conv_trunk / dense_head split (the smallnet refactor the sweep rides on)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ("ref", "fixed", "fixed_pallas", "int8"))
def test_apply_equals_trunk_plus_head(params, backend):
    rng = np.random.default_rng(2)
    imgs = rng.random((4, 28, 28, 1)).astype(np.float32)
    whole = np.asarray(smallnet.apply(params, imgs, backend=backend))
    feats = smallnet.conv_trunk(params, imgs, backend=backend)
    split = np.asarray(smallnet.dense_head(params, feats, backend=backend))
    if np.issubdtype(whole.dtype, np.integer):
        np.testing.assert_array_equal(split, whole)
    else:
        np.testing.assert_array_equal(split, whole)  # same ops, same order


def test_conv_trunk_shapes(params):
    imgs = np.zeros((2, 28, 28, 1), np.float32)
    assert smallnet.conv_trunk(params, imgs, backend="ref").shape == (2, 7, 7, 1)
    assert smallnet.conv_trunk(params, imgs, backend="fixed").shape == (2, 7, 7)


# ---------------------------------------------------------------------------
# Tiler.confidence_grid regression (satellite): non-product position lists
# ---------------------------------------------------------------------------

def test_confidence_grid_rejects_non_product_positions():
    t = Tiler()
    scores = np.full((3, 10), 0.5, np.float32)
    with pytest.raises(ValueError, match="rectangular"):
        t.confidence_grid(scores, [(0, 0), (0, 14), (14, 7)])


def test_confidence_grid_derives_cols_from_positions():
    t = Tiler()
    pos = [(y, x) for y in (0, 14) for x in (0, 14, 28)]
    grid = t.confidence_grid(np.tile(np.linspace(0, 1, 10, dtype=np.float32),
                                     (6, 1)), pos)
    assert grid.shape == (2, 3)


# ---------------------------------------------------------------------------
# window head: static slices of the role maps == the element-wise gather
# ---------------------------------------------------------------------------

def _gather_features(quad, positions, patch=28):
    """The head's former formulation, kept as the reference: index each
    pooled role map with (Nw, k, k) window indices, then pick the map of
    each feature's role with nested selects."""
    k = patch // 4
    gy = jnp.asarray([y // 4 for y, _ in positions])
    gx = jnp.asarray([x // 4 for _, x in positions])
    off = jnp.arange(k)
    rows = gy[:, None, None] + off[None, :, None]
    cols = gx[:, None, None] + off[None, None, :]
    last_row = (off == k - 1)[None, :, None]
    last_col = (off == k - 1)[None, None, :]
    I, Bm, R, C = (m[0] for m in quad)
    feats = jnp.where(last_row & last_col, C[rows, cols],
                      jnp.where(last_row, Bm[rows, cols],
                                jnp.where(last_col, R[rows, cols],
                                          I[rows, cols])))
    return feats.reshape(len(positions), -1)


def _random_quad(shape, seed):
    rng = np.random.default_rng(seed)
    H, W = shape
    return tuple(jnp.asarray(rng.integers(np.iinfo(np.int32).min,
                                          np.iinfo(np.int32).max,
                                          (1, H // 4, W // 4), np.int32,
                                          endpoint=True))
                 for _ in range(4))


@pytest.mark.parametrize("stride", (4, 8, 12))
@pytest.mark.parametrize("shape", ((112, 112), (120, 160), (480, 640)))
def test_slice_head_equals_gather_head(params, shape, stride):
    """Every frame/stride pair, clamped tail or none (112 at stride 8 has
    one, at 4 and 12 none; 120x160 at 12 has one on rows only): the
    sliced features and the head's scores equal the gather's word for
    word on random int32 role maps."""
    pos = tuple(tile_positions(shape, 28, stride))
    quad = _random_quad(shape, seed=shape[0] + stride)
    lattice = fs._window_lattice(28, pos)
    want = _gather_features(quad, pos)
    got = fs._window_features(quad, lattice)
    assert got.shape == (len(pos), 49)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    be = B.get_backend("fixed")
    p = be.prepare_params(params)
    np.testing.assert_array_equal(
        np.asarray(fs._head_scores(be, p, quad, lattice)),
        np.asarray(smallnet.dense_head(p, want, backend=be)))


def _lattice(ys, xs):
    return [(y, x) for y in ys for x in xs]


@pytest.mark.parametrize("positions", (
    _lattice((0, 8), (0, 8))[:-1],                         # a window missing
    _lattice((0, 8), (0, 8))[::-1],                        # not row-major
    _lattice((0, 8, 12, 24), (0, 8)),                      # uneven rows
    _lattice((0, 8), (0, 8, 16, 20, 24)),                  # two tail steps
    _lattice((0, 6), (0, 8)),                              # off the pool grid
), ids=("missing", "order", "uneven", "two_tails", "off_grid"))
def test_slice_head_rejects_non_lattice_positions(positions):
    with pytest.raises(ValueError):
        fs._window_lattice(28, tuple(positions))


def test_head_program_has_no_gather(params):
    """The lowered head program at 112x112 reads windows by static slices:
    a change that brings the element-wise gather back fails here (the
    reference's own lowering shows the check can see one)."""
    pos = tuple(tile_positions((112, 112), 28, 8))
    quad = _random_quad((112, 112), seed=0)
    head = fs.make_head_fn("fixed_pallas", (112, 112), 28, 8)
    assert "gather" not in head.lower(params, quad).as_text()
    assert "gather" in jax.jit(_gather_features, static_argnums=1).lower(
        quad, pos).as_text()
