"""Fully-convolutional frame sweep: the conv trunk ONCE per frame, on device.

`Tiler` re-convolves overlapping pixels up to 4x and extracts every 28x28
window with host-side numpy; this module instead runs smallNet's
conv->sigmoid->pool->conv->sigmoid->pool trunk over the WHOLE HxW frame in
one jitted device call per frame (any registered backend, including the
fused `fixed`/`fixed_pallas` stages), then scores every 28x28 window
from its 7x7 block of the pooled feature maps and applies the 49->10 dense
head — 49 static strided slices (one per window feature, across all
windows at once) + one `fixed_dense`/matmul instead of N host-extracted
patches.  This is the ZynqNet/Solovyev-style "evaluate the
CNN once over the full frame" deployment the ROADMAP called for.

Exactness contract (the reason this file is mostly about padding):
Patch-wise scoring SAME-pads each 28x28 window (Keras even-kernel
convention: 0 before, 1 after), so a window's last-row/-col features are
computed against ZEROS even when the window sits mid-frame with real pixels
below/right of it.  A naive full-frame trunk uses those real pixels and
diverges from `Tiler` on 13 of every window's 49 features.  The sweep
therefore tracks FOUR role maps per stage ("quad cascade"):

    I  value at a patch position when it is interior (not last row/col)
    B  value when the position is in the patch's last ROW
    R  value when it is in the patch's last COLUMN
    C  value when it is the bottom-right corner

The edge maps are computed frame-wide through the backend's own conv
primitives with MASKED WEIGHTS — a zeroed tap contributes exactly 0 to the
MAC in every word domain, which is precisely what the patch's padding zeros
contribute — and maps that mix sources (e.g. a conv reading interior rows
above a last-row) are decomposed into per-source masked convs recombined
with `Backend.accumulate` (wraparound fixed-point addition is associative
mod 2**bits, so the recombined accumulator word is bit-identical to the
single-conv word).  Scoring a window then selects, per feature, the map
matching that feature's role.  Result: window scores are WORD-EXACT vs
`Tiler.extract`+`score` for the integer backends (interior AND border
windows alike) and float-tight (~1 ulp, XLA conv accumulation order) for
the float backends, so sweep-vs-tiler detection parity on a frozen clip is
a theorem, not a tuning outcome.

Edge/geometry contract (validated loudly, tested in tests/test_fcn_sweep.py):

  * window positions must sit on the pooled-map lattice: y % 4 == x % 4 == 0
    (two 2x2/2 pools -> stride-4 granularity).  `stride` must be a multiple
    of 4 and the frame must satisfy (H - patch) % 4 == 0 (equivalently
    H % 4 == 0 for patch 28) so the edge-clamped last window of
    `tile_positions` is on the lattice; anything else raises ValueError.
  * the positions must be the row-major product of evenly stepped row
    and column starts, each with at most one clamped last start (what
    `tile_positions` builds): the head reads windows as static slices.
  * `patch` must be a multiple of 4 (the deployed dense head fixes it at
    28: 49 pooled features).
  * saturating fixed-point configs are rejected (saturation is not
    associative, so the decomposed accumulation could drift); the
    registered `fixed`/`fixed_pallas` backends use the hardware-faithful
    wraparound mode, which is exact.

Launch topology: the composed cascade dispatches O(stages x role-maps)
kernel launches per frame on the Pallas substrates (4 single-source + 5
mixed-source convs at level 1, plus pools and PLAN units).  On the fixed
substrates the whole quad trunk also exists as ONE tiled Pallas launch
(`kernels/frame_trunk`), reached through `Backend.frame_trunk`.  The
backend picks the route from the frame: `_trunk_quad` takes the one-launch
trunk wherever the hook returns one (fixed substrates, single frames with
extents a multiple of 4, wraparound words) and the composed cascade,
`composed_trunk_quad`, everywhere else.

`FcnSweep` is `Tiler`-compatible: `positions` / `extract` / `score` /
`confidence_grid` / `aggregate` / `detect` have the same shapes and
semantics (`extract` returns the frame itself as a single "tile" batch),
so the streaming pipeline's confidence grid, dedup, and `Detection` output
run unchanged — `StreamingPipeline` just routes the per-frame device call
through `FcnSweep.score` instead of an engine wave when `tiler.sweep`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, ClassVar, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import backends as B
from repro.core import runtime
from repro.core import smallnet
from repro.obs import trace as T
from repro.streaming.sources import Frame
from repro.streaming.tiler import Tiler, tile_positions

_POOL = 4          # two 2x2/2 pools: pooled-map granularity in frame pixels


def _mask(rows, cols) -> np.ndarray:
    """(2,2) 0/1 tap mask from per-axis keep flags."""
    return np.asarray(rows, np.int32)[:, None] * np.asarray(cols, np.int32)[None, :]

# tap masks: keep (row0|row1) x (col0|col1) of the 2x2 kernel
_TOP, _BOT = (1, 0), (0, 1)
_ALL = (1, 1)


def _pool_mix(even_rows, odd_rows):
    """2x2/2 pool whose even input rows come from `even_rows` and odd rows
    from `odd_rows` (maps are (B,H,W) fixed words or (B,H,W,1) float NHWC;
    pooling is over axes 1,2).  Pure comparisons — exact in every domain."""
    e, o = even_rows, odd_rows
    return jnp.maximum(jnp.maximum(e[:, ::2, ::2], e[:, ::2, 1::2]),
                       jnp.maximum(o[:, 1::2, ::2], o[:, 1::2, 1::2]))


def _pool_quadrants(tl, tr, bl, br):
    """2x2/2 pool with a distinct source map per window quadrant:
    (2r,2c) from tl, (2r,2c+1) from tr, (2r+1,2c) from bl, (2r+1,2c+1)
    from br."""
    return jnp.maximum(jnp.maximum(tl[:, ::2, ::2], tr[:, ::2, 1::2]),
                       jnp.maximum(bl[:, 1::2, ::2], br[:, 1::2, 1::2]))


def _sweep_stage(be: B.Backend, quad, w, b):
    """One conv->activation->pool stage over the role-map quad.

    Role bookkeeping: for a patch of side N at this stage, conv output row
    N-2 ("prelast") reads input rows N-2 (interior) and N-1 (last row ->
    the B map); conv output row N-1 ("last") reads input row N-1 (B map)
    and the patch's SAME-padding zeros, realized by masking the bottom
    taps.  The pooled last row then combines the prelast (even) and last
    (odd) conv rows.  Columns are symmetric with the R map; the corner
    walks the same lattice through C.
    """
    I, Bm, R, C = quad
    zb = jnp.zeros_like(b)
    w_top = be.mask_conv_weight(w, _mask(_TOP, _ALL))
    w_bot = be.mask_conv_weight(w, _mask(_BOT, _ALL))
    w_left = be.mask_conv_weight(w, _mask(_ALL, _TOP))
    w_right = be.mask_conv_weight(w, _mask(_ALL, _BOT))
    w_00 = be.mask_conv_weight(w, _mask(_TOP, _TOP))
    w_01 = be.mask_conv_weight(w, _mask(_TOP, _BOT))
    w_10 = be.mask_conv_weight(w, _mask(_BOT, _TOP))
    w_11 = be.mask_conv_weight(w, _mask(_BOT, _BOT))

    # single-source role maps: one fused conv+activation launch each
    s_ii = be.fused_conv_act(I, w, b)                    # all taps interior
    s_li = be.sigmoid(be.conv2x2_same(Bm, w_top, b))     # last row
    s_il = be.sigmoid(be.conv2x2_same(R, w_left, b))     # last col
    s_ll = be.sigmoid(be.conv2x2_same(C, w_00, b))       # corner
    if Bm is I and R is I and C is I:
        # level 0: pixels are role-independent, so every mixed-source map
        # collapses onto a single-source one (the masks partition the full
        # kernel over one source; for fixed words this is the associativity
        # argument again, for floats it IS the patch's single-conv sum) —
        # the full-resolution stage runs 4 conv launches instead of 13
        s_pi = s_ip = s_pp = s_ii
        s_pl, s_lp = s_il, s_li
    else:
        # mixed-source maps: masked partial convs recombined pre-activation
        s_pi = be.sigmoid(be.accumulate(                 # prelast row
            be.conv2x2_same(I, w_top, b), be.conv2x2_same(Bm, w_bot, zb)))
        s_ip = be.sigmoid(be.accumulate(                 # prelast col
            be.conv2x2_same(I, w_left, b), be.conv2x2_same(R, w_right, zb)))
        s_pp = be.sigmoid(be.accumulate(be.accumulate(be.accumulate(
            be.conv2x2_same(I, w_00, b),                 # prelast/prelast
            be.conv2x2_same(R, w_01, zb)),
            be.conv2x2_same(Bm, w_10, zb)),
            be.conv2x2_same(C, w_11, zb)))
        s_pl = be.sigmoid(be.accumulate(                 # prelast row, last col
            be.conv2x2_same(R, w_00, b), be.conv2x2_same(C, w_10, zb)))
        s_lp = be.sigmoid(be.accumulate(                 # last row, prelast col
            be.conv2x2_same(Bm, w_00, b), be.conv2x2_same(C, w_01, zb)))

    return (be.maxpool2x2(s_ii),                         # interior
            _pool_mix(s_pi, s_li),                       # last pooled row
            _pool_quadrants(s_ip, s_il, s_ip, s_il),     # last pooled col
            _pool_quadrants(s_pp, s_pl, s_lp, s_ll))     # pooled corner


def _squeeze_map(x):
    """(1,H,W) fixed words or (1,H,W,1) float NHWC -> (H,W)."""
    return x[0, ..., 0] if x.ndim == 4 else x[0]


def composed_trunk_quad(be: B.Backend, p: dict, frames):
    """Both conv stages of the sweep as the composed per-stage cascade over
    one (1,H,W,1) float frame batch: the level-2 role-map quad (I, B, R,
    C), each (1, H/4, W/4[, 1]).  The route of every backend without a
    whole-frame `frame_trunk`, and the reference the one-launch trunk
    must match word for word."""
    x = be.ingest(frames)
    quad = (x, x, x, x)      # pixels are role-independent at level 0
    quad = _sweep_stage(be, quad, p["conv1"]["w"], p["conv1"]["b"])
    return _sweep_stage(be, quad, p["conv2"]["w"], p["conv2"]["b"])


def _trunk_quad(be: B.Backend, p: dict, frames):
    """The sweep's trunk: the backend's one-launch `frame_trunk` where it
    has one for these frames, else `composed_trunk_quad`.  The single
    trunk definition shared by the jitted scorer and the golden-pinned
    `sweep_feature_maps` view."""
    quad = be.frame_trunk(frames, p)
    return quad if quad is not None else composed_trunk_quad(be, p, frames)


def _check_saturation(be: B.Backend) -> None:
    cfg = getattr(be, "cfg", None)
    if cfg is not None and getattr(cfg, "saturate", False):
        raise NotImplementedError(
            "FcnSweep requires a wraparound fixed-point config: saturating "
            "addition is not associative, so the sweep's decomposed edge-map "
            "accumulation could drift from the patch-wise words.  The "
            "registered 'fixed'/'fixed_pallas' backends use wraparound mode.")


class _Lattice(NamedTuple):
    """The static window lattice of one sweep on the pooled maps: window
    side `k` in pooled cells, and per axis the window starts as
    (first, step, n, tail) -- `n` starts `first + i*step`, then the
    edge-clamped `tail` start (None where the clamp lands on the step)."""
    k: int
    rows: tuple[int, int, int, int | None]
    cols: tuple[int, int, int, int | None]


def _axis_starts(starts: list[int]) -> tuple[int, int, int, int | None]:
    """Frame-pixel window starts along one axis -> the pooled
    (first, step, n, tail) that `_Lattice` holds."""
    if any(s % _POOL for s in starts):
        raise ValueError(
            f"window starts {starts} are off the stride-{_POOL} pooled "
            f"lattice")
    g = [s // _POOL for s in starts]
    step = g[1] - g[0] if len(g) > 1 else 1
    body, tail = g, None
    if len(g) > 2 and g[-1] - g[-2] != step:
        body, tail = g[:-1], g[-1]
    if step < 1 or body != list(range(g[0], g[0] + step * len(body), step)) \
            or (tail is not None and tail <= body[-1]):
        raise ValueError(
            f"window starts {starts} are not an evenly stepped lattice "
            f"with at most one clamped last start")
    return g[0], step, len(body), tail


def _window_lattice(patch: int,
                    positions: tuple[tuple[int, int], ...]) -> _Lattice:
    """The static lattice for scoring `positions` from a pooled role-map
    quad.  `positions` must be the row-major product of its distinct row
    and column starts, each an evenly stepped run plus at most one
    clamped last start (what `tile_positions` builds); anything else
    raises ValueError, as `Tiler.confidence_grid` does."""
    ys = list(dict.fromkeys(y for y, _ in positions))
    xs = list(dict.fromkeys(x for _, x in positions))
    if [tuple(p) for p in positions] != [(y, x) for y in ys for x in xs]:
        raise ValueError(
            f"the sweep head needs the row-major product of the window "
            f"row and column starts: {len(positions)} positions are not "
            f"{len(ys)} rows x {len(xs)} cols in that order")
    return _Lattice(patch // _POOL, _axis_starts(ys), _axis_starts(xs))


def _take(m, axis: int, starts: tuple[int, int, int, int | None], off: int):
    """Elements `start + off` of `m` along `axis` for every lattice start:
    one strided static slice, plus the clamped tail as a one-wide slice."""
    first, step, n, tail = starts
    lo = first + off
    x = lax.slice_in_dim(m, lo, lo + (n - 1) * step + 1, step, axis)
    if tail is None:
        return x
    return jnp.concatenate(
        [x, lax.slice_in_dim(m, tail + off, tail + off + 1, 1, axis)], axis)


def _window_features(quad, lattice: _Lattice):
    """Role-map quad -> (Nw, k*k) window features, windows row-major and
    features `dy*k + dx`.  Feature (dy, dx) of every window comes from the
    one role map its offset selects (C at the corner, B on the last row,
    R on the last column, I inside), as static slices of that map: no
    gather, no select."""
    k, rows, cols = lattice
    I2, B2, R2, C2 = (_squeeze_map(m) for m in quad)
    planes = []
    for dy in range(k):
        for dx in range(k):
            m = ((C2 if dx == k - 1 else B2) if dy == k - 1
                 else (R2 if dx == k - 1 else I2))
            planes.append(_take(_take(m, 0, rows, dy), 1, cols, dx))
    # (k*k, Ny, Nx) -> (Ny, Nx, k*k): one transpose after the planes is
    # cheaper on the TPU v5e than stacking them on the lane axis
    return jnp.stack(planes).transpose(1, 2, 0).reshape(-1, k * k)


def _head_scores(be: B.Backend, p: dict, quad, lattice: _Lattice):
    """The sweep's dense-head half as traced code: role-map quad + static
    lattice -> (Nw, 10) backend-native scores.  Shared verbatim by the
    monolithic `_sweep_fn` and the disaggregated head program
    (`make_head_fn`), so splitting the sweep across engine pools cannot
    change a single word on the integer substrates."""
    return smallnet.dense_head(p, _window_features(quad, lattice),
                               backend=be)


@functools.lru_cache(maxsize=64)
def _sweep_fn(be: B.Backend, frame_shape: tuple[int, int], patch: int,
              positions: tuple[tuple[int, int], ...]):
    """Jitted whole-sweep function for one (backend, geometry): params +
    (1,H,W,1) float frame -> (n_windows, 10) backend-native scores, ONE
    device call per frame."""
    lattice = _window_lattice(patch, positions)

    def run(params, frame):
        p = be.prepare_params(params)
        quad = _trunk_quad(be, p, frame)
        return _head_scores(be, p, quad, lattice)

    return jax.jit(run)


@functools.lru_cache(maxsize=32)
def make_trunk_fn(backend: str):
    """Jitted TRUNK half of the sweep for a registered backend: (params,
    (1,H,W,1) float frame) -> the level-2 role-map quad (I, B, R, C) in
    the backend's native domain.  This is the heavy per-frame stage the
    disaggregated serving layer (`serving/disagg.py`) runs on its trunk
    pool and caches per frame digest; `make_head_fn` scores windows from
    the result."""
    be = B.get_backend(backend)
    _check_saturation(be)

    def run(params, frames):
        p = be.prepare_params(params)
        return _trunk_quad(be, p, frames)

    return jax.jit(run)


@functools.lru_cache(maxsize=64)
def make_head_fn(backend: str, patch: int,
                 positions: tuple[tuple[int, int], ...]):
    """Jitted HEAD half of the sweep: (params, role-map quad) -> (Nw, 10)
    backend-native window scores for a fixed window lattice.  Runs the
    SAME traced window slices + dense head as the monolithic `_sweep_fn`
    (`_head_scores`), so head-pool scores from a cached feature quad are
    int32 word-exact vs the one-call sweep on the fixed substrates."""
    be = B.get_backend(backend)
    _check_saturation(be)
    lattice = _window_lattice(patch, positions)

    def run(params, quad):
        p = be.prepare_params(params)
        return _head_scores(be, p, quad, lattice)

    return jax.jit(run)


# flipping the process-wide interpret switch must drop programs compiled
# under the old mode (core/runtime.py documents the staleness hazard)
runtime.register_reset_hook(_sweep_fn.cache_clear)
runtime.register_reset_hook(make_trunk_fn.cache_clear)
runtime.register_reset_hook(make_head_fn.cache_clear)


def _feature_maps(trunk, params: Any, frame, backend: str | B.Backend):
    be = B.get_backend(backend)
    _check_saturation(be)
    f = jnp.asarray(np.asarray(frame, np.float32))
    if f.ndim == 2:
        f = f[..., None]
    quad = trunk(be, be.prepare_params(params), f[None])
    names = ("interior", "last_row", "last_col", "corner")
    return {n: np.asarray(_squeeze_map(m)) for n, m in zip(names, quad)}


def sweep_feature_maps(params: Any, frame: np.ndarray | jnp.ndarray, *,
                       backend: str | B.Backend = "ref"):
    """The level-2 role-map quad for one (H,W[,1]) frame: a dict of
    (H/4, W/4) pooled feature maps {"interior", "last_row", "last_col",
    "corner"} in the backend's native domain (Qm.n int32 words for the
    fixed substrates).  This is the sweep trunk, on the backend's route,
    without the dense head — what the golden vectors freeze."""
    return _feature_maps(_trunk_quad, params, frame, backend)


def composed_feature_maps(params: Any, frame: np.ndarray | jnp.ndarray, *,
                          backend: str | B.Backend = "ref"):
    """`sweep_feature_maps` through `composed_trunk_quad` on every
    backend: the per-stage decomposition's own words, which the one-launch
    trunk must equal."""
    return _feature_maps(composed_trunk_quad, params, frame, backend)


@dataclasses.dataclass(frozen=True)
class FcnSweep(Tiler):
    """Drop-in `Tiler` that scores windows from one full-frame trunk pass.

    Same knobs and aggregation semantics as `Tiler`; `stride` must be a
    multiple of 4 (pooled-map granularity) and defaults to 8 — finer than
    the host tiler's 14 because sweep windows are nearly free.  `extract`
    returns the frame itself as a (1,H,W,1) "tile" batch (the mass gate
    computes per-window means from it), and `score` runs the jitted sweep:
    one device call per frame on any registered backend.  Inside that
    call the trunk is the backend's one-launch `frame_trunk` where it has
    one (the fixed substrates) and the composed role-map cascade
    elsewhere; both give the same words.
    """
    stride: int = 8
    sweep: ClassVar[bool] = True

    def __post_init__(self):
        if self.patch % _POOL:
            raise ValueError(
                f"FcnSweep patch must be a multiple of {_POOL} "
                f"(two 2x2/2 pools), got {self.patch}")
        if self.stride % _POOL:
            raise ValueError(
                f"FcnSweep stride must be a multiple of {_POOL}: window "
                f"positions live on the pooled-map lattice (got "
                f"{self.stride})")

    def positions(self, frame_shape: tuple[int, int]) -> list[tuple[int, int]]:
        H, W = frame_shape
        if (H - self.patch) % _POOL or (W - self.patch) % _POOL:
            raise ValueError(
                f"frame {frame_shape} breaks the sweep edge contract: the "
                f"edge-clamped last window at (H-{self.patch}, W-"
                f"{self.patch}) must sit on the stride-{_POOL} pooled "
                f"lattice, i.e. (H - patch) % {_POOL} == 0 on both axes "
                f"(pad or crop the frame to a multiple of {_POOL})")
        return tile_positions(frame_shape, self.patch, self.stride)

    def extract(self, frame: Frame | np.ndarray) -> tuple[np.ndarray,
                                                          list[tuple[int, int]]]:
        """Frame -> ((1, H, W, 1) float32 frame batch, window positions).
        No host-side patch materialization — that is the whole point."""
        px = frame.pixels if isinstance(frame, Frame) else np.asarray(frame)
        if px.ndim == 2:
            px = px[..., None]
        pos = self.positions(px.shape[:2])
        return np.ascontiguousarray(px[None], np.float32), pos

    def score(self, params: Any, frames: np.ndarray, *,
              backend: str | B.Backend = "ref") -> np.ndarray:
        """One jitted full-frame trunk pass + windowed dense head:
        (1, H, W, 1) frame -> (n_windows, 10) backend-native scores, in
        `positions` order.  Profiler regions: `sweep.score`, holding
        `sweep.prepare` (frame array, positions, program lookup),
        `sweep.upload`, `sweep.dispatch` (the call until it returns) and
        `sweep.fetch` (the wait for the scores and their copy back)."""
        with T.region("sweep.score") as top:
            with T.region("sweep.prepare", parent=top):
                be = B.get_backend(backend)
                _check_saturation(be)
                frames = np.asarray(frames, np.float32)
                if frames.ndim == 3:
                    frames = frames[None]
                if frames.shape[0] != 1:
                    raise ValueError(
                        f"FcnSweep.score takes one frame per call (the sweep "
                        f"is a per-frame device program), got batch "
                        f"{frames.shape[0]}")
                H, W = frames.shape[1], frames.shape[2]
                pos = tuple(self.positions((H, W)))
                fn = _sweep_fn(be, (H, W), self.patch, pos)
            with T.region("sweep.upload", parent=top):
                x = jnp.asarray(frames)
            with T.region("sweep.dispatch", parent=top):
                out = fn(params, x)
            with T.region("sweep.fetch", parent=top):
                return np.asarray(out)

    def _masses(self, tiles: np.ndarray,
                positions: Sequence[tuple[int, int]]) -> np.ndarray:
        """Per-window mean pixel intensity from the frame itself: one
        strided-view gather instead of a per-window host loop (same
        elements in the same row-major reduction order as `Tiler`'s
        per-tile means — asserted by the mass-gate parity test)."""
        frame = np.asarray(tiles, np.float32)[0, ..., 0]
        p = self.patch
        wins = np.lib.stride_tricks.sliding_window_view(frame, (p, p))
        ys = np.fromiter((y for y, _ in positions), np.intp)
        xs = np.fromiter((x for _, x in positions), np.intp)
        return wins[ys, xs].mean(axis=(-2, -1), dtype=np.float32)
