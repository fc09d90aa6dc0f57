"""Numpy int64 oracle for the trunk megakernel.

Composes the `kernels/fixed_conv/ref.py` primitives (full-64-bit products,
explicit wraps — no limb tricks) into the quad-role trunk exactly as
`streaming/fcn_sweep._sweep_stage` structures it: level 0 collapses onto 4
masked-tap maps, level 1 runs the full 9-map mixed-source stage with
masked partial convs recombined by wraparound `fixed_add_ref` in the same
association order.  The Pallas megakernel, the composed sweep, and this
module are three independent routes to the same int32 words; the test
battery pins each pair so a bug in the kernel's tiling/halo bookkeeping
cannot hide behind a matching bug in the sweep (or vice versa).

The oracle is deliberately UNTILED — one whole-frame computation — so it
knows nothing about halos, window offsets, or edge masking: exactly the
things the megakernel must get right to match it.
"""
from __future__ import annotations

import numpy as np

from repro.core.fixed_point import FixedPointConfig, Q16_16
from repro.kernels.fixed_conv.ref import (fixed_add_ref, fixed_conv2d_ref,
                                          fixed_sigmoid_plan_ref)

# tap masks over the row-major (4,) kernel, mirroring fcn_sweep._mask
_M_ALL = np.array([1, 1, 1, 1], np.int64)
_M_TOP = np.array([1, 1, 0, 0], np.int64)      # keep kernel row 0
_M_BOT = np.array([0, 0, 1, 1], np.int64)
_M_LEFT = np.array([1, 0, 1, 0], np.int64)     # keep kernel col 0
_M_RIGHT = np.array([0, 1, 0, 1], np.int64)
_M_00 = np.array([1, 0, 0, 0], np.int64)
_M_01 = np.array([0, 1, 0, 0], np.int64)
_M_10 = np.array([0, 0, 1, 0], np.int64)
_M_11 = np.array([0, 0, 0, 1], np.int64)


def _pool_mix_ref(e, o):
    """(B,H,W) -> (B,H/2,W/2): even output rows pool `e`, odd rows `o`."""
    return np.maximum(np.maximum(e[:, ::2, ::2], e[:, ::2, 1::2]),
                      np.maximum(o[:, 1::2, ::2], o[:, 1::2, 1::2]))


def _pool_quadrants_ref(tl, tr, bl, br):
    return np.maximum(np.maximum(tl[:, ::2, ::2], tr[:, ::2, 1::2]),
                      np.maximum(bl[:, 1::2, ::2], br[:, 1::2, 1::2]))


def frame_trunk_quad_ref(x: np.ndarray, w1: np.ndarray, b1, w2: np.ndarray,
                         b2, cfg: FixedPointConfig = Q16_16) -> np.ndarray:
    """x (H, W) int words; w1/w2 (4,) row-major taps; b1/b2 scalar bias
    words.  Returns the (4, H/4, W/4) int64 level-2 quad
    [interior, last_row, last_col, corner]."""
    if cfg.saturate:
        raise NotImplementedError("oracle requires wraparound configs, "
                                  "like the megakernel it pins")
    x = np.asarray(x, np.int64)[None]              # (1, H, W)
    w1 = np.asarray(w1, np.int64).reshape(4)
    w2 = np.asarray(w2, np.int64).reshape(4)
    b1 = np.int64(np.asarray(b1).reshape(-1)[0])
    b2 = np.int64(np.asarray(b2).reshape(-1)[0])

    def conv(src, w4, mask, bias):
        return fixed_conv2d_ref(src, w4 * mask, bias, cfg)

    def plan(y):
        return fixed_sigmoid_plan_ref(y, cfg)

    def add(a, b):
        return fixed_add_ref(a, b, cfg)

    # level 0: role-independent pixels, collapsed quad
    s_ii = plan(conv(x, w1, _M_ALL, b1))
    s_li = plan(conv(x, w1, _M_TOP, b1))
    s_il = plan(conv(x, w1, _M_LEFT, b1))
    s_ll = plan(conv(x, w1, _M_00, b1))
    I1 = _pool_mix_ref(s_ii, s_ii)
    B1 = _pool_mix_ref(s_ii, s_li)
    R1 = _pool_quadrants_ref(s_ii, s_il, s_ii, s_il)
    C1 = _pool_quadrants_ref(s_ii, s_il, s_li, s_ll)

    # level 1: full mixed-source stage, _sweep_stage's association order
    z = np.int64(0)
    s_ii2 = plan(conv(I1, w2, _M_ALL, b2))
    s_li2 = plan(conv(B1, w2, _M_TOP, b2))
    s_il2 = plan(conv(R1, w2, _M_LEFT, b2))
    s_ll2 = plan(conv(C1, w2, _M_00, b2))
    s_pi2 = plan(add(conv(I1, w2, _M_TOP, b2), conv(B1, w2, _M_BOT, z)))
    s_ip2 = plan(add(conv(I1, w2, _M_LEFT, b2), conv(R1, w2, _M_RIGHT, z)))
    s_pp2 = plan(add(add(add(conv(I1, w2, _M_00, b2),
                             conv(R1, w2, _M_01, z)),
                         conv(B1, w2, _M_10, z)),
                     conv(C1, w2, _M_11, z)))
    s_pl2 = plan(add(conv(R1, w2, _M_00, b2), conv(C1, w2, _M_10, z)))
    s_lp2 = plan(add(conv(B1, w2, _M_00, b2), conv(C1, w2, _M_01, z)))

    return np.stack([
        _pool_mix_ref(s_ii2, s_ii2)[0],
        _pool_mix_ref(s_pi2, s_li2)[0],
        _pool_quadrants_ref(s_ip2, s_il2, s_ip2, s_il2)[0],
        _pool_quadrants_ref(s_pp2, s_pl2, s_lp2, s_ll2)[0],
    ])
