"""Tiled whole-frame trunk megakernel: smallNet's conv->PLAN->pool->conv->
PLAN->pool pipeline over a big frame in ONE Pallas launch.

The paper's headline is a single hand-fused hardware stage that never
leaves the datapath; the PR-5 frame sweep reproduced its arithmetic but
still dispatched O(stages x role-maps) separate launches per frame (4
single-source + 5 mixed-source conv launches at level 1, plus pools).
This kernel is the ZynqNet/Solovyev-style whole-frame tiled dataflow: the
grid walks spatial frame tiles, each program instance

  input          receives its tile PLUS a `HALO`-wide apron of rows/cols
                 of the (zero-padded) frame as one VMEM block — overlapping
                 reads are inexpressible as a blocked `BlockSpec`, so the
                 wrapper lays the frame out as a (H/th, W/tw, th+HALO,
                 tw+HALO) stack of halo windows and the grid pipeline DMAs
                 one window per program (a manual DMA at element offsets
                 would need (8, 128)-aligned window shapes); the four conv
                 taps and the bias word of each stage ride in SMEM
  level 0        4 masked-tap conv+PLAN maps over the tile extent + 2
                 (interior / last-row / last-col / corner, the quad-role
                 cascade of streaming/fcn_sweep.py), pooled 2x2/2 into the
                 level-1 quad WITH one halo row/col kept, then frame-edge
                 rows/cols zeroed (they realize level 1's SAME padding)
  level 1        the 9 role maps (4 single-source + 5 mixed-source masked
                 convs recombined with wraparound `fixed_add`, in exactly
                 `_sweep_stage`'s association order), PLAN, pooled into the
                 (4, th/4, tw/4) output quad tile

entirely in int32 Qm.n words, reusing the SAME `core/fixed_point` helpers
as `kernels/fixed_conv` (16-bit-limb MAC, wraparound adds,
`shift_right_round`, PLAN shift-add) and the same `kernels/pooling`
selects — so the megakernel cannot drift from the per-stage kernels it
replaces.  Word-exactness vs the composed sweep is
an associativity argument, not a tolerance: every masked partial conv wraps
its accumulator into the Qm.n word exactly where `backends.conv_fixed`
does, and wraparound addition is associative mod 2**total_bits (saturating
configs are rejected by ops.py for exactly this reason).

Why the halo is 3: level-0 convs at the tile's last row read 1 row down
(2x2 kernel), the level-1 quad keeps 1 pooled halo row (= 2 more level-0
conv rows, i.e. input rows), and level-1 convs read 1 pooled row down —
3 input rows/cols past the tile on the bottom/right, 0 on the top/left
(the SAME convention is 0-before/1-after, so tiles never look up-left).

Each program writes its quad tile as one whole (4, th/4, tw/4) block of a
(H/th, W/tw, 4, th/4, tw/4) output, so the block always equals the array's
last dims and meets Mosaic's (8, 128) block rule for any tile; the wrapper
reassembles the (4, H/4, W/4) quad outside the kernel.

Interpret mode is bit-identical to compiled mode for the same reason as
kernels/fixed_conv: every op is integer with exactly one defined result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixed_point as fxp
from repro.kernels.pooling import pool_mix, pool_quadrants

HALO = 3                      # input rows/cols of bottom/right apron per tile

_TAPS = ((0, 0), (0, 1), (1, 0), (1, 1))   # (dh, dw), row-major like w.reshape(4)

# tap-index subsets of the 2x2 kernel, mirroring fcn_sweep's weight masks
_T_ALL = (0, 1, 2, 3)
_T_TOP = (0, 1)               # keep kernel row 0   (w_top)
_T_BOT = (2, 3)               # keep kernel row 1   (w_bot)
_T_LEFT = (0, 2)              # keep kernel col 0   (w_left)
_T_RIGHT = (1, 3)             # keep kernel col 1   (w_right)
_T_00, _T_01, _T_10, _T_11 = (0,), (1,), (2,), (3,)


def _conv(x, w_ref, taps, bias, cfg, Ho, Wo):
    """Masked-tap fixed conv over a local block: per-tap limb MAC with
    plain int32 accumulation, then ONE `fixed_add` folding in the bias (or
    a zero word) — the exact accumulator structure of `backends.conv_fixed`
    / `kernels/fixed_conv`, so each partial conv lands on the same Qm.n
    word the composed sweep computes.  Skipped taps contribute exactly what
    a zeroed weight would (fixed_mul(x, 0) == 0 in every format)."""
    acc = jnp.zeros((Ho, Wo), jnp.int32)
    for t in taps:
        dh, dw = _TAPS[t]
        win = x[dh:dh + Ho, dw:dw + Wo]
        w = jnp.full((Ho, Wo), w_ref[t], jnp.int32)   # SMEM tap, broadcast
        acc = acc + fxp.fixed_mul(win, w, cfg)
    return fxp.fixed_add(acc, jnp.full((Ho, Wo), bias, jnp.int32), cfg)


def _frame_trunk_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref, *,
                        cfg: fxp.FixedPointConfig,
                        th: int, tw: int, H: int, W: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...]              # (th+HALO, tw+HALO) window of the padded frame

    def plan(y):
        return fxp.fixed_sigmoid_plan(y, cfg)

    def add(a, b):
        return fxp.fixed_add(a, b, cfg)

    b1 = b1_ref[0]
    b2 = b2_ref[0]
    zero = jnp.int32(0)

    # -- level 0: pixels are role-independent, so the quad collapses onto
    # 4 masked-tap maps (fcn_sweep's level-0 collapse), computed over the
    # tile extent + 2 so the level-1 quad keeps one pooled halo row/col
    h0, w0 = th + HALO - 1, tw + HALO - 1
    s_ii = plan(_conv(x, w1_ref, _T_ALL, b1, cfg, h0, w0))
    s_li = plan(_conv(x, w1_ref, _T_TOP, b1, cfg, h0, w0))
    s_il = plan(_conv(x, w1_ref, _T_LEFT, b1, cfg, h0, w0))
    s_ll = plan(_conv(x, w1_ref, _T_00, b1, cfg, h0, w0))

    I1 = pool_mix(s_ii, s_ii)                        # interior
    B1 = pool_mix(s_ii, s_li)                        # last row
    R1 = pool_quadrants(s_ii, s_il, s_ii, s_il)      # last col
    C1 = pool_quadrants(s_ii, s_il, s_li, s_ll)      # corner

    # -- frame-edge masking: a level-1 position at global row H/2 / col W/2
    # exists only as this tile's halo over the frame's zero padding; its
    # conv words are bias+PLAN garbage, but semantically it IS level 1's
    # SAME zero padding — so zero it.  Interior tiles' halos hold their
    # right/down neighbor's real values and pass through untouched.
    h1, w1 = th // 2 + 1, tw // 2 + 1
    rows = i * (th // 2) + jax.lax.broadcasted_iota(jnp.int32, (h1, w1), 0)
    cols = j * (tw // 2) + jax.lax.broadcasted_iota(jnp.int32, (h1, w1), 1)
    keep = (rows < H // 2) & (cols < W // 2)
    I1, B1, R1, C1 = (jnp.where(keep, m, zero) for m in (I1, B1, R1, C1))

    # -- level 1: the full 9-map mixed-source stage, masked partial convs
    # recombined with wraparound adds in _sweep_stage's association order
    h2, w2 = th // 2, tw // 2
    c = functools.partial(_conv, cfg=cfg, Ho=h2, Wo=w2)
    s_ii2 = plan(c(I1, w2_ref, _T_ALL, b2))
    s_li2 = plan(c(B1, w2_ref, _T_TOP, b2))
    s_il2 = plan(c(R1, w2_ref, _T_LEFT, b2))
    s_ll2 = plan(c(C1, w2_ref, _T_00, b2))
    s_pi2 = plan(add(c(I1, w2_ref, _T_TOP, b2), c(B1, w2_ref, _T_BOT, zero)))
    s_ip2 = plan(add(c(I1, w2_ref, _T_LEFT, b2),
                     c(R1, w2_ref, _T_RIGHT, zero)))
    s_pp2 = plan(add(add(add(c(I1, w2_ref, _T_00, b2),
                             c(R1, w2_ref, _T_01, zero)),
                         c(B1, w2_ref, _T_10, zero)),
                     c(C1, w2_ref, _T_11, zero)))
    s_pl2 = plan(add(c(R1, w2_ref, _T_00, b2), c(C1, w2_ref, _T_10, zero)))
    s_lp2 = plan(add(c(B1, w2_ref, _T_00, b2), c(C1, w2_ref, _T_01, zero)))

    o_ref[0] = pool_mix(s_ii2, s_ii2)                        # interior
    o_ref[1] = pool_mix(s_pi2, s_li2)                        # last row
    o_ref[2] = pool_quadrants(s_ip2, s_il2, s_ip2, s_il2)    # last col
    o_ref[3] = pool_quadrants(s_pp2, s_pl2, s_lp2, s_ll2)    # corner


def frame_trunk_pallas(xp: jnp.ndarray, w1: jnp.ndarray, b1: jnp.ndarray,
                       w2: jnp.ndarray, b2: jnp.ndarray, *,
                       cfg: fxp.FixedPointConfig = fxp.Q16_16,
                       th: int, tw: int,
                       interpret: bool) -> jnp.ndarray:
    """xp (H+HALO, W+HALO) int32 frame pre-padded with HALO zero rows/cols
    bottom+right; w1/w2 (4,) int32 taps; b1/b2 (1,) int32 bias words;
    (th, tw) the tile extent (each divides H/W, multiples of 4).  Returns
    the (4, H/4, W/4) int32 level-2 role-map quad
    [interior, last_row, last_col, corner] in ONE launch."""
    H, W = xp.shape[0] - HALO, xp.shape[1] - HALO
    nI, nJ = H // th, W // tw
    kern = functools.partial(_frame_trunk_kernel, cfg=cfg, th=th, tw=tw,
                             H=H, W=W)
    windows = jnp.stack([
        jnp.stack([xp[i * th:i * th + th + HALO, j * tw:j * tw + tw + HALO]
                   for j in range(nJ)])
        for i in range(nI)])                   # (nI, nJ, th+HALO, tw+HALO)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    tiles = pl.pallas_call(
        kern,
        grid=(nI, nJ),
        in_specs=[
            pl.BlockSpec((None, None, th + HALO, tw + HALO),
                         lambda i, j: (i, j, 0, 0)),
            smem, smem, smem, smem,
        ],
        out_specs=pl.BlockSpec((None, None, 4, th // 4, tw // 4),
                               lambda i, j: (i, j, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nI, nJ, 4, th // 4, tw // 4),
                                       jnp.int32),
        interpret=interpret,
    )(windows, w1, b1, w2, b2)
    return tiles.transpose(2, 0, 3, 1, 4).reshape(4, H // 4, W // 4)
