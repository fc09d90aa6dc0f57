"""Sliding-window tiler: full frames -> 28x28 patches -> detections.

The classifier only ever sees 28x28; a frame is swept by a window at a
configurable stride, every patch is scored in ONE batched `smallnet.apply`
call on any registered backend, and per-patch scores aggregate into a
confidence grid from which thresholded, deduplicated detections with frame
coordinates are extracted.

Patch extraction here is host-side numpy, which re-convolves overlapping
pixels up to 4x — the baseline path.  `streaming/fcn_sweep.FcnSweep` is the
drop-in fully-convolutional alternative that runs the conv trunk ONCE over
the whole frame on device and scores every window from the pooled feature
map, word-exact with this tiler on the fixed substrates (the former ROADMAP
follow-up, landed).

Determinism contract: for integer-scored backends ("fixed"/"fixed_pallas")
the int32 Qm.n words are converted on the host exactly as `from_fixed`
converts them — identical words give identical floats give identical
detections, so the two fixed substrates are detection-bit-exact on a frozen
clip (asserted in tests).
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import smallnet
from repro.obs import metrics as M
from repro.obs import trace as T
from repro.streaming.sources import Frame


@dataclasses.dataclass(frozen=True)
class Detection:
    """One deduplicated hit: class label + the winning patch's frame coords."""
    label: int
    score: float
    y: int                           # top-left of the 28x28 patch
    x: int
    size: int = 28

    @property
    def center(self) -> tuple[float, float]:
        return (self.y + self.size / 2, self.x + self.size / 2)


def tile_positions(frame_shape: tuple[int, int], patch: int,
                   stride: int) -> list[tuple[int, int]]:
    """Top-left (y, x) of every window; the last row/col is clamped to the
    frame edge so coverage is complete even when stride doesn't divide."""
    H, W = frame_shape
    if H < patch or W < patch:
        raise ValueError(f"frame {frame_shape} smaller than patch {patch}")
    ys = list(range(0, H - patch, stride)) + [H - patch]
    xs = list(range(0, W - patch, stride)) + [W - patch]
    return [(y, x) for y in ys for x in xs]


@dataclasses.dataclass(frozen=True)
class Tiler:
    """Window extraction + score aggregation for one (patch, stride) sweep.

    `threshold` is on the backend's sigmoid confidence in (0, 1);
    `min_dist` is the Chebyshev distance (px) at or under which two hits are
    the same object (defaults to one stride — adjacent windows over one
    digit collapse to the strongest).  `min_mass` > 0 additionally gates
    windows on mean pixel intensity: the classifier never saw empty
    background in training, so without the gate it happily "detects" digits
    in noise — mass is a pure function of the (identical) float tiles, so
    the gate preserves cross-substrate detection bit-exactness.  Off by
    default.
    """
    patch: int = 28
    stride: int = 14
    threshold: float = 0.9
    min_dist: int = 14
    min_mass: float = 0.0
    cfg: fxp.FixedPointConfig = fxp.Q16_16   # word format of integer scores

    # subclasses that score from a full-frame sweep instead of host-extracted
    # patches (streaming/fcn_sweep.FcnSweep) flip this; the pipeline routes
    # the per-frame device call accordingly
    sweep: ClassVar[bool] = False

    def positions(self, frame_shape: tuple[int, int]) -> list[tuple[int, int]]:
        return tile_positions(frame_shape, self.patch, self.stride)

    def extract(self, frame: Frame | np.ndarray) -> tuple[np.ndarray,
                                                          list[tuple[int, int]]]:
        """Frame -> (N, patch, patch, 1) float32 tile batch + positions."""
        px = frame.pixels if isinstance(frame, Frame) else np.asarray(frame)
        if px.ndim == 2:
            px = px[..., None]
        pos = self.positions(px.shape[:2])
        p = self.patch
        tiles = np.stack([px[y:y + p, x:x + p] for y, x in pos])
        return np.ascontiguousarray(tiles, np.float32), pos

    def score(self, params: Any, tiles: np.ndarray, *,
              backend: str | B.Backend = "ref") -> np.ndarray:
        """One batched forward over every tile: (N, patch, patch, 1) ->
        (N, 10) backend-native class scores."""
        return np.asarray(smallnet.apply(params, jnp.asarray(tiles),
                                         backend=backend))

    def _as_float(self, scores: np.ndarray) -> np.ndarray:
        """Backend-native scores of any shape -> float confidences.  Integer
        words convert on the host, bit for bit what `fxp.from_fixed` gives:
        int -> float32 rounds to nearest and `cfg.scale` is a power of two.
        Float scores pass through."""
        scores = np.asarray(scores)
        if np.issubdtype(scores.dtype, np.integer):
            return scores.astype(np.float32) / np.float32(self.cfg.scale)
        return scores

    def _confidences(self, scores: np.ndarray) -> np.ndarray:
        """Backend-native (N, 10) scores -> float sigmoid confidences, on
        the host (`pipeline.confidences`)."""
        with T.region("pipeline.confidences"):
            return self._as_float(scores)

    def confidence_grid(self, scores: np.ndarray,
                        positions: Sequence[tuple[int, int]]) -> np.ndarray:
        """(N, 10) scores -> (n_rows, n_cols) map of per-window max
        confidence, in sweep order (the detector's heatmap view).

        The grid is only well-defined for a full rectangular sweep: the
        column count is derived from the distinct x positions and checked
        against the row count, so a non-product position list (e.g. a
        future foreground-gated sparse sweep) fails loudly instead of
        silently reshaping into a garbled heatmap."""
        conf = self._confidences(scores).max(axis=-1)
        n_rows = len({y for y, _ in positions})
        n_cols = len({x for _, x in positions})
        if n_rows * n_cols != len(positions):
            raise ValueError(
                f"confidence_grid needs a full rectangular position grid: "
                f"{len(positions)} positions cannot tile "
                f"{n_rows} rows x {n_cols} cols")
        return conf.reshape(n_rows, n_cols)

    def _masses(self, tiles: np.ndarray,
                positions: Sequence[tuple[int, int]]) -> np.ndarray:
        """Per-window mean pixel intensity for the `min_mass` gate.  Here
        `tiles` is the (N, patch, patch, 1) batch; the FCN sweep overrides
        this to compute the same means from the frame itself."""
        return np.asarray(tiles, np.float32).reshape(len(tiles), -1).mean(1)

    def aggregate(self, scores: np.ndarray,
                  positions: Sequence[tuple[int, int]],
                  tiles: np.ndarray | None = None) -> list[Detection]:
        """Threshold + greedy dedup: strongest window wins, any window whose
        top-left is within `min_dist` (Chebyshev, INCLUSIVE — adjacent
        windows at the default stride collapse) of an accepted detection is
        suppressed regardless of label.  Ties break on (y, x) so the result
        is a pure function of the score words.  Pass `tiles` to apply the
        `min_mass` foreground gate.

        Whole arrays until the threshold: `pipeline.confidences` converts
        only each window's maximum word (the conversion is monotone, so it
        is the maximum confidence); `pipeline.select` thresholds that
        vector, and only the windows over it reach Python, each labelled by
        the argmax of its converted row (first index on a tie).  The
        registry counters `aggregate_windows` / `aggregate_candidates`
        count both sides; with tracing on, `pipeline.select` carries them
        as tags.  `scores` is not modified."""
        with T.region("pipeline.confidences"):
            scores = np.asarray(scores)
            best = self._as_float(scores.max(axis=-1))
        with T.region("pipeline.select") as span:
            if self.min_mass > 0.0 and tiles is not None:
                mass = self._masses(tiles, positions)
                best = np.where(mass >= self.min_mass, best, -1.0)
            cand = np.flatnonzero(best >= self.threshold)
            labels = self._as_float(scores[cand]).argmax(axis=-1)
            hits = [(s, positions[i][0], positions[i][1], lab)
                    for i, s, lab in zip(cand.tolist(), best[cand].tolist(),
                                         labels.tolist())]
            M.REGISTRY.counter(M.AGG_WINDOWS).inc(len(best))
            M.REGISTRY.counter(M.AGG_CANDIDATES).inc(len(cand))
            if span is not None:
                span.tags["windows"] = len(best)
                span.tags["candidates"] = len(cand)
            hits.sort(key=lambda h: (-h[0], h[1], h[2]))
            out: list[Detection] = []
            for s, y, x, lab in hits:
                if any(max(abs(y - d.y), abs(x - d.x)) <= self.min_dist
                       for d in out):
                    continue
                out.append(Detection(label=lab, score=s, y=y, x=x,
                                     size=self.patch))
            return out

    def detect(self, params: Any, frame: Frame | np.ndarray, *,
               backend: str | B.Backend = "ref") -> list[Detection]:
        """The offline (non-pipelined) path: extract -> score -> aggregate.
        The pipeline must produce exactly this for every frame it serves."""
        tiles, pos = self.extract(frame)
        return self.aggregate(self.score(params, tiles, backend=backend),
                              pos, tiles)
