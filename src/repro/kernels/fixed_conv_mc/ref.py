"""Numpy int64 oracle of the multi-channel fixed-point conv.

Direct loops over the taps and input channels of the SAME-padded input
(no patch matrix), each product through `fixed_conv.ref.fixed_mul_ref`,
int32 accumulation, the wrap to the word width, then the bias add — the
contract `fixed_point.fixed_matmul` + `fixed_add` state for one output
word.
"""
from __future__ import annotations

import numpy as np

from repro.core.fixed_point import FixedPointConfig, Q16_16
from repro.kernels.fixed_conv.ref import (fixed_add_ref, fixed_mul_ref,
                                          wrap_bits_ref)


def fixed_conv_mc_ref(x: np.ndarray, w: np.ndarray, b: np.ndarray,
                      cfg: FixedPointConfig = Q16_16, *,
                      stride: int = 1) -> np.ndarray:
    """x (B,H,W,Cin), w (kh,kw,Cin,Cout), b (Cout,) words ->
    (B,Ho,Wo,Cout) words, TensorFlow SAME padding."""
    x = np.asarray(x, np.int64)
    w = np.asarray(w, np.int64)
    B, H, W, C = x.shape
    kh, kw, _, N = w.shape
    Ho, Wo = -(-H // stride), -(-W // stride)
    ph = max((Ho - 1) * stride + kh - H, 0)
    pw = max((Wo - 1) * stride + kw - W, 0)
    xp = np.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                    (pw // 2, pw - pw // 2), (0, 0)))
    acc = np.zeros((B, Ho, Wo, N), np.int64)
    for dy in range(kh):
        for dx in range(kw):
            win = xp[:, dy:dy + (Ho - 1) * stride + 1:stride,
                     dx:dx + (Wo - 1) * stride + 1:stride, :]
            for c in range(C):
                acc += fixed_mul_ref(win[..., c:c + 1], w[dy, dx, c], cfg)
    acc = wrap_bits_ref(wrap_bits_ref(acc, 32), cfg.total_bits)
    return fixed_add_ref(acc, np.asarray(b, np.int64), cfg)
