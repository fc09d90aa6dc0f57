"""Streaming subsystem: sources, tiler, and the deadline-scheduled pipeline.

The load-bearing invariants: clips replay deterministically, the pipeline
serves EXACTLY what the offline tiler computes, every frame is accounted
(in == served + dropped, never silently lost), bounded queues stay bounded
under a too-fast source, and the two fixed-point substrates produce
bit-identical detections on a frozen clip.
"""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixed_point as fxp
from repro.core import smallnet
from repro.obs import metrics as M
from repro.obs import trace as T
from repro.serving.router import ReplicaRouter
from repro.serving.vision_engine import VisionEngine
from repro.streaming.pipeline import StreamConfig, StreamingPipeline
from repro.streaming.sources import PacedPlayer, SyntheticVideoSource
from repro.streaming import tiler as tiler_mod
from repro.streaming.tiler import Detection, Tiler, tile_positions


@pytest.fixture(scope="module")
def params():
    return smallnet.seeded_params()


@pytest.fixture(scope="module")
def clip():
    return SyntheticVideoSource(n_frames=8, seed=3)


@pytest.fixture(scope="module")
def tiler(params, clip):
    """Threshold at the 80th pct of first-frame 'fixed' confidences, so the
    frozen clip deterministically yields nonzero detections."""
    t0 = Tiler(stride=14)
    tiles, _ = t0.extract(clip.frames()[0])
    conf = t0._confidences(t0.score(params, tiles, backend="fixed")).max(-1)
    return Tiler(stride=14, threshold=float(np.quantile(conf, 0.8)))


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

def test_source_replays_identical_clip(clip):
    a, b = clip.frames(), clip.frames()
    assert len(a) == len(b) == 8
    for fa, fb in zip(a, b):
        np.testing.assert_array_equal(fa.pixels, fb.pixels)
        assert fa.truth == fb.truth


def test_source_tracks_stay_in_bounds_and_move(clip):
    H, W = clip.frame_shape
    frames = clip.frames()
    for f in frames:
        assert f.pixels.shape == (H, W, 1)
        assert f.pixels.min() >= 0.0 and f.pixels.max() <= 1.0
        for box in f.truth:
            assert 0 <= box.y and box.y + box.h <= H
            assert 0 <= box.x and box.x + box.w <= W
    # the objects drift: at least one box center changes across the clip
    c0 = [b.center for b in frames[0].truth]
    cN = [b.center for b in frames[-1].truth]
    assert c0 != cN


# ---------------------------------------------------------------------------
# tiler
# ---------------------------------------------------------------------------

def test_tile_positions_cover_frame():
    pos = tile_positions((112, 112), 28, 14)
    assert len(pos) == 49                        # 7x7 sweep
    covered = np.zeros((112, 112), bool)
    for y, x in pos:
        covered[y:y + 28, x:x + 28] = True
    assert covered.all()
    # non-dividing stride: last window clamps to the edge, still covers
    pos = tile_positions((100, 90), 28, 24)
    assert max(y for y, _ in pos) == 72 and max(x for _, x in pos) == 62
    covered = np.zeros((100, 90), bool)
    for y, x in pos:
        covered[y:y + 28, x:x + 28] = True
    assert covered.all()


def test_tiler_extract_matches_slicing(clip):
    frame = clip.frames()[0]
    t = Tiler(stride=28)
    tiles, pos = t.extract(frame)
    assert tiles.shape == (len(pos), 28, 28, 1) and tiles.dtype == np.float32
    for tile, (y, x) in zip(tiles, pos):
        np.testing.assert_array_equal(tile, frame.pixels[y:y + 28, x:x + 28])


def test_aggregate_thresholds_and_dedups():
    t = Tiler(stride=14, threshold=0.9, min_dist=14)
    pos = [(0, 0), (0, 14), (0, 70), (56, 56)]
    scores = np.full((4, 10), 0.1, np.float32)
    scores[0, 3] = 0.95           # hit
    scores[1, 3] = 0.97           # stronger hit 14px away -> wins, 0 suppressed
    scores[2, 7] = 0.93           # distinct object
    scores[3, 5] = 0.50           # below threshold
    dets = t.aggregate(scores, pos)
    assert [(d.label, d.y, d.x) for d in dets] == [(3, 0, 14), (7, 0, 70)]
    assert dets[0].score == pytest.approx(0.97)


def test_aggregate_min_mass_gates_empty_windows():
    t = Tiler(stride=14, threshold=0.9, min_mass=0.05)
    pos = [(0, 0), (0, 70)]
    scores = np.full((2, 10), 0.99, np.float32)       # both confident...
    tiles = np.zeros((2, 28, 28, 1), np.float32)
    tiles[1] += 0.2                                   # ...only one has pixels
    dets = t.aggregate(scores, pos, tiles)
    assert [(d.y, d.x) for d in dets] == [(0, 70)]
    # without tiles the gate is a no-op
    assert len(t.aggregate(scores, pos)) == 2


def _loop_aggregate(t, scores, positions, tiles=None):
    """The per-window aggregate as it was: integer words through
    `fxp.from_fixed` on the device, then one Python pass over every
    window.  The plain reference the whole-array aggregate must equal."""
    scores = np.asarray(scores)
    if np.issubdtype(scores.dtype, np.integer):
        scores = np.asarray(fxp.from_fixed(jnp.asarray(scores), t.cfg))
    labels = scores.argmax(axis=-1)
    best = scores.max(axis=-1)
    if t.min_mass > 0.0 and tiles is not None:
        mass = np.asarray(tiles, np.float32).reshape(len(tiles), -1).mean(1)
        best = np.where(mass >= t.min_mass, best, -1.0)
    hits = [(float(best[i]), positions[i][0], positions[i][1], int(labels[i]))
            for i in range(len(positions)) if best[i] >= t.threshold]
    hits.sort(key=lambda h: (-h[0], h[1], h[2]))
    out = []
    for s, y, x, lab in hits:
        if any(max(abs(y - d.y), abs(x - d.x)) <= t.min_dist for d in out):
            continue
        out.append(Detection(label=lab, score=s, y=y, x=x, size=t.patch))
    return out


_AGG_POS = tile_positions((140, 140), 28, 8)          # 15 x 15 windows


def _agg_scores(kind: str, threshold: float, seed: int) -> np.ndarray:
    """Random (N, 10) scores in `kind`'s format (`q16` / `q8` words or
    `float`) with the cases the whole-array aggregate must get right:
    ties in the window maximum and the argmax, scores one step either
    side of (and on) the threshold, and, for Q16.16, words past 2**24
    that round to one float."""
    rng = np.random.default_rng(seed)
    n = len(_AGG_POS)
    if kind == "float":
        s = rng.uniform(0.0, 1.0, (n, 10)).astype(np.float32)
        thr = np.float32(threshold)
        near = [np.nextafter(thr, np.float32(-1)), thr,
                np.nextafter(thr, np.float32(2))]
    else:
        cfg = fxp.Q16_16 if kind == "q16" else fxp.Q8_8
        lo, hi = (-(1 << 31), (1 << 31) - 1) if kind == "q16" \
            else (-(1 << 15), (1 << 15) - 1)
        w = int(np.ceil(threshold * cfg.scale))
        s = rng.integers(-2 * int(cfg.scale), int(cfg.scale), (n, 10),
                         dtype=np.int64)
        s[rng.random((n, 10)) < 0.05] = lo
        near = [w - 1, w, w + 1]
        if kind == "q16":                     # one float, different words
            s[:20, 2], s[:20, 5] = 1 << 25, (1 << 25) + 1
            s[20:30, 7] = hi
        s = s.astype(np.int32)
    if threshold > 1e4:                       # no score reaches it
        near = []
    for k, v in enumerate(near):              # around the threshold
        rows = slice(40 + 20 * k, 60 + 20 * k)
        s[rows, 3] = v
        s[rows.start:rows.start + 10, 8] = v   # a tie in the argmax
    s[120:140] = s[100]                       # ties in the window maximum
    return s


@pytest.mark.parametrize("kind", ["q16", "q8", "float"])
@pytest.mark.parametrize("threshold", [0.9, 58983 / 65536, -1.0, 1e5])
@pytest.mark.parametrize("mass", [False, True])
def test_aggregate_matches_the_per_window_loop(kind, threshold, mass):
    cfg = fxp.Q8_8 if kind == "q8" else fxp.Q16_16
    t = Tiler(stride=8, threshold=threshold, cfg=cfg,
              min_mass=0.3 if mass else 0.0)
    tiles = None
    if mass:
        rng = np.random.default_rng(7)
        tiles = rng.uniform(0.0, 0.6, (len(_AGG_POS), 28, 28, 1)
                            ).astype(np.float32)
    cands = M.REGISTRY.counter(M.AGG_CANDIDATES)
    for seed in range(3):
        scores = _agg_scores(kind, threshold, seed)
        kept = scores.copy()
        want = _loop_aggregate(t, scores, _AGG_POS, tiles)
        n = cands.value
        got = t.aggregate(scores, _AGG_POS, tiles)
        assert got == want
        np.testing.assert_array_equal(scores, kept)     # not mutated
        if threshold == -1.0:          # every window, gated ones included
            assert cands.value - n == len(_AGG_POS) and got
        if threshold > 1e4:
            assert cands.value == n and got == []


@pytest.mark.parametrize("cfg", [fxp.Q16_16, fxp.Q8_8])
def test_confidences_equal_from_fixed_bit_for_bit(cfg):
    rng = np.random.default_rng(1)
    w = rng.integers(-(1 << 31), (1 << 31) - 1, (500, 10), dtype=np.int64)
    w[:5] = [[-(1 << 31), (1 << 31) - 1, 0, 1, -1,
              (1 << 24) + 1, (1 << 25) + 3, -(1 << 24) - 1, 3, 58983]] * 5
    w = w.astype(np.int32)
    got = Tiler(cfg=cfg)._confidences(w)
    want = np.asarray(fxp.from_fixed(jnp.asarray(w), cfg))
    assert got.dtype == want.dtype == np.float32
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))


def test_integer_aggregate_makes_no_device_call(monkeypatch):
    t = Tiler(stride=8, threshold=0.9)
    pos = tile_positions((140, 140), 28, 8)
    words = _agg_scores("q16", 0.9, 0)
    want = _loop_aggregate(t, words, pos)
    want_grid = np.asarray(fxp.from_fixed(jnp.asarray(words))).max(-1)

    def refuse(*a, **k):
        raise AssertionError("device call in the aggregate stage")

    monkeypatch.setattr(tiler_mod.fxp, "from_fixed", refuse)
    monkeypatch.setattr(tiler_mod.jnp, "asarray", refuse)
    assert want and t.aggregate(words, pos) == want
    np.testing.assert_array_equal(t.confidence_grid(words, pos),
                                  want_grid.reshape(15, 15))


def test_aggregate_counts_windows_and_candidates():
    t = Tiler(stride=8, threshold=0.9)
    words = np.zeros((len(_AGG_POS), 10), np.int32)
    words[[3, 50, 51], 4] = 60_000             # 0.9155 > 0.9
    words[9, 1] = 58_982                       # 0.89999 < 0.9
    windows = M.REGISTRY.counter(M.AGG_WINDOWS)
    cands = M.REGISTRY.counter(M.AGG_CANDIDATES)
    n_w, n_c = windows.value, cands.value
    tr = T.enable(capacity=16)
    try:
        t.aggregate(words, _AGG_POS)
    finally:
        T.disable()
    assert windows.value - n_w == len(_AGG_POS)
    assert cands.value - n_c == 3
    (sel,) = [s for s in tr.recorder.spans() if s.name == "pipeline.select"]
    assert sel.tags == {"windows": len(_AGG_POS), "candidates": 3}


# ---------------------------------------------------------------------------
# pipeline
# ---------------------------------------------------------------------------

def test_pipeline_serves_exactly_the_offline_detections(params, clip, tiler):
    eng = VisionEngine(params, backend="ref", batch_size=64, warmup=False)
    pipe = StreamingPipeline(clip, eng, tiler)
    res = pipe.run()
    s = pipe.stats()
    assert s["mode"] == "throughput"
    assert s["frames_served"] == len(clip) and s["frames_dropped"] == 0
    assert s["accounted"]
    offline = [tiler.detect(params, f, backend="ref") for f in clip.frames()]
    assert [r.detections for r in res] == offline
    assert s["detections_total"] == sum(len(d) for d in offline) > 0
    assert 0.0 < s["batch_occupancy"] <= 1.0


def test_deadline_misses_are_counted_not_lost(params, clip, tiler):
    eng = VisionEngine(params, backend="ref", batch_size=64, warmup=False)
    pipe = StreamingPipeline(
        PacedPlayer(clip, fps=100), eng, tiler,
        config=StreamConfig(deadline_ms=1e-3, queue_size=4))
    res = pipe.run()
    s = pipe.stats()
    assert res == [] and s["frames_served"] == 0
    assert s["frames_dropped"] == s["frames_in"] == len(clip)
    assert s["drops_by_reason"] == {"deadline": len(clip)}
    assert s["accounted"]


@dataclasses.dataclass
class _FakeResult:
    scores: np.ndarray


class _SlowEngine:
    """Stub inference: fixed per-wave delay, constant scores."""

    def __init__(self, delay_s: float):
        self.delay_s = delay_s

    def serve(self, tiles):
        time.sleep(self.delay_s)
        return [_FakeResult(scores=np.zeros(10, np.float32)) for _ in tiles]


def test_backpressure_bounds_queue_depth_under_fast_source(tiler):
    clip = SyntheticVideoSource(n_frames=20, seed=1)
    pipe = StreamingPipeline(
        PacedPlayer(clip, fps=500), _SlowEngine(0.02), tiler,
        config=StreamConfig(queue_size=2))
    pipe.run()
    s = pipe.stats()
    assert s["mode"] == "realtime"
    assert max(s["queue_hwm"].values()) <= 2
    assert s["drops_by_reason"].get("queue_full", 0) > 0
    assert s["accounted"]
    assert s["frames_served"] + s["frames_dropped"] == 20


def test_drop_policy_oldest_keeps_the_freshest_frames(tiler):
    clip = SyntheticVideoSource(n_frames=20, seed=1)
    pipe = StreamingPipeline(
        PacedPlayer(clip, fps=500), _SlowEngine(0.02), tiler,
        config=StreamConfig(queue_size=2, drop_policy="oldest"))
    res = pipe.run()
    s = pipe.stats()
    assert s["accounted"] and s["drops_by_reason"].get("queue_full", 0) > 0
    # evicting the stalest queued frame means the clip's LAST frame is
    # always admitted and served
    assert res and res[-1].index == 19


def test_fixed_vs_fixed_pallas_detections_bit_identical(params, clip, tiler):
    """The frozen-clip contract: identical int32 score words -> identical
    detections (labels, coordinates, AND float scores) on both fixed
    substrates, through the full pipeline."""
    results = {}
    for backend in ("fixed", "fixed_pallas"):
        eng = VisionEngine(params, backend=backend, batch_size=64,
                           warmup=False)
        pipe = StreamingPipeline(clip, eng, tiler)
        pipe.run()
        assert pipe.stats()["accounted"]
        assert pipe.stats()["frames_served"] == len(clip)
        results[backend] = [r.detections for r in pipe.results]
    assert sum(len(d) for d in results["fixed"]) > 0
    assert results["fixed"] == results["fixed_pallas"]


def test_engine_batch_occupancy(params):
    eng = VisionEngine(params, backend="ref", batch_size=4, warmup=False)
    eng.serve([np.zeros((28, 28, 1), np.float32)] * 5)   # 2 steps, 3 padded
    s = eng.stats()
    assert s["batches"] == 2 and s["padded_slots"] == 3
    assert s["batch_occupancy"] == pytest.approx(5 / 8)


@pytest.mark.slow
def test_router_soak_reconciles_every_frame(params, tiler):
    """Several hundred frames through a 2-replica router: frames in ==
    served + dropped, and the fleet saw exactly one wave of tiles per
    served frame."""
    clip = SyntheticVideoSource(n_frames=300, seed=11)
    router = ReplicaRouter.from_backends(params, ["ref", "ref"],
                                        batch_size=64, warmup=False)
    pipe = StreamingPipeline(
        PacedPlayer(clip, fps=40), router, tiler,
        config=StreamConfig(deadline_ms=500, queue_size=4))
    pipe.run()
    s = pipe.stats()
    assert s["accounted"]
    assert s["frames_served"] + s["frames_dropped"] == 300
    n_tiles = len(tiler.positions(clip.frame_shape))
    assert s["engine"]["n"] == s["frames_served"] * n_tiles
    assert s["frames_served"] > 0
