"""jit'd public wrapper + VMEM-budget tile chooser for the trunk megakernel.

`frame_trunk_quad` is the one-launch trunk: (H, W) int32 frame words ->
(4, H/4, W/4) level-2 role-map quad [interior, last_row, last_col, corner],
word-exact with the composed FcnSweep trunk (streaming/fcn_sweep.py) and
with the per-stage kernels/fixed_conv launches it replaces.

Tile-size selection (`choose_tile`) is a static VMEM budget computation:
for a candidate (th, tw) tile the kernel's resident int32 words are

    (th+halo)(tw+halo)           input tile + bottom/right halo apron
  + 11 (th+halo-1)(tw+halo-1)    4 level-0 conv/PLAN maps + the worst-case
                                 ~7 limb temporaries of one tap's fixed mul
  + 4 (th/2+1)(tw/2+1)           level-1 quad incl. its pooled halo row/col
  + 16 (th/2)(tw/2)              9 level-1 role maps + limb temporaries
  + 4 (th/4)(tw/4)               the output quad tile

all x4 bytes (`frame_trunk_vmem_bytes`).  The chooser scans tile extents
that divide the frame and are multiples of 4 (two 2x2/2 pools), keeping
the largest-area tile that fits the 14 MB budget — a 112x112 frame runs as
one tile (~900 KB), 512x512 splits into two 512x256 tiles (~9 MB each), so
the acceptance-bar 512 frame genuinely exercises tile seams.  The model is
conservative: the TPU compiler (AOT for a described v5e) accepts tiles the
model rejects, 512x512 and 1024x512 among them, under its default scoped
VMEM limit, because it does not keep every map resident at once.

Off-chip traffic follows from the same geometry: n_tiles x
(th+HALO)(tw+HALO) input words DMA'd HBM->VMEM (the halo apron is
genuinely re-read at tile seams) plus the 4 x (H/4)(W/4) output quad
written back — nothing else leaves the core, about 20x fewer bytes than
the composed sweep's per-launch HBM round-trips.  `analysis/mfu.py`
(`trunk_workload(..., "sweep_megakernel")`) models this count from
`choose_tile`/`HALO`.

Geometry contract (loud, tested in tests/test_frame_trunk_props.py): the
frame must have H % 4 == W % 4 == 0 and be at least 4x4 — the same pooled
lattice the sweep itself requires — and saturating fixed-point configs are
rejected (the megakernel's decomposed accumulation leans on wraparound
associativity exactly like the composed sweep).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import fixed_point as fxp
from repro.core import runtime
from repro.kernels.frame_trunk.kernel import HALO, frame_trunk_pallas

_VMEM_BUDGET = 14 * 2 ** 20  # leave headroom out of ~16 MB/core


def frame_trunk_vmem_bytes(th: int, tw: int, *, halo: int = HALO) -> int:
    """Resident VMEM bytes for one (th, tw) tile program (see module
    docstring for the breakdown)."""
    h0, w0 = th + halo - 1, tw + halo - 1         # level-0 conv extent
    words = ((th + halo) * (tw + halo)
             + 11 * h0 * w0
             + 4 * (th // 2 + 1) * (tw // 2 + 1)
             + 16 * (th // 2) * (tw // 2)
             + 4 * (th // 4) * (tw // 4))
    return 4 * words


def _tile_candidates(n: int) -> list[int]:
    """Divisors of n that are multiples of 4, largest first."""
    return [d for d in range(n, 3, -1) if n % d == 0 and d % 4 == 0]


def check_frame_geometry(H: int, W: int) -> None:
    """The pooled-lattice contract every trunk entry point shares."""
    if H < 4 or W < 4:
        raise ValueError(
            f"frame {H}x{W} is too small to tile: the trunk pools 4x in "
            f"each dim, so frames must be at least 4x4")
    if H % 4 or W % 4:
        raise ValueError(
            f"frame {H}x{W} breaks the pooled-lattice contract: two 2x2/2 "
            f"pools need H % 4 == W % 4 == 0 (pad or crop the frame)")


def choose_tile(H: int, W: int, *, halo: int = HALO,
                budget: int = _VMEM_BUDGET) -> tuple[int, int]:
    """Largest-area (th, tw) tile that divides the (H, W) frame on the
    pooled lattice and fits the VMEM budget.  Deterministic: ties prefer
    the squarer tile, then the taller one."""
    check_frame_geometry(H, W)
    best = None
    for th in _tile_candidates(H):
        for tw in _tile_candidates(W):
            if frame_trunk_vmem_bytes(th, tw, halo=halo) > budget:
                continue
            key = (th * tw, min(th, tw), th)
            if best is None or key > best[0]:
                best = (key, (th, tw))
    if best is None:
        raise ValueError(
            f"VMEM budget {budget} B cannot fit even a 4x4 tile of the "
            f"{H}x{W} frame "
            f"({frame_trunk_vmem_bytes(4, 4, halo=halo)} B needed)")
    return best[1]


@functools.partial(jax.jit, static_argnames=("cfg", "th", "tw", "interpret"))
def _frame_trunk_jit(x, w1, b1, w2, b2, *, cfg, th, tw, interpret):
    xp = jnp.pad(x.astype(jnp.int32), ((0, HALO), (0, HALO)))
    return frame_trunk_pallas(
        xp, w1.reshape(4).astype(jnp.int32), b1.reshape(1).astype(jnp.int32),
        w2.reshape(4).astype(jnp.int32), b2.reshape(1).astype(jnp.int32),
        cfg=cfg, th=th, tw=tw, interpret=interpret)


def frame_trunk_quad(x: jnp.ndarray, w1: jnp.ndarray, b1: jnp.ndarray,
                     w2: jnp.ndarray, b2: jnp.ndarray, *,
                     cfg: fxp.FixedPointConfig = fxp.Q16_16,
                     tile: tuple[int, int] | None = None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Both trunk stages over one (H, W) int32 word frame in ONE launch:
    returns the (4, H/4, W/4) int32 quad [interior, last_row, last_col,
    corner].  w1/w2 are the (2,2,1,1) or (4,) int32 conv taps, b1/b2 the
    bias words.  `tile=None` picks the tile via `choose_tile`; an explicit
    (th, tw) must divide the frame on the pooled lattice (tests use small
    forced tiles to exercise seams on small frames).  `interpret=None`
    follows `core.runtime` (the process-wide interpret default)."""
    H, W = x.shape
    check_frame_geometry(H, W)
    if cfg.saturate:
        raise NotImplementedError(
            "frame_trunk requires a wraparound fixed-point config: "
            "saturating addition is not associative, so the megakernel's "
            "decomposed masked-conv accumulation could drift from the "
            "composed words (same contract as FcnSweep)")
    if tile is None:
        th, tw = choose_tile(H, W)
    else:
        th, tw = tile
        if th % 4 or tw % 4 or th < 4 or tw < 4 or H % th or W % tw:
            raise ValueError(
                f"tile {th}x{tw} must be multiples of 4 dividing the "
                f"{H}x{W} frame")
    return _frame_trunk_jit(x, w1, b1, w2, b2, cfg=cfg, th=th, tw=tw,
                            interpret=runtime.resolve_interpret(interpret))
