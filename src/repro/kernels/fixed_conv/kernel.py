"""Fused Qm.n fixed-point conv pipeline as a Pallas kernel — the paper's
Verilog datapath (§III-B, Fig. 4) as ONE kernel launch, entirely in int32.

Pipeline stages, fused per program instance (one image per grid step):

  windowing      -> four static shifted VMEM views of the SAME-padded block
                    (the Verilog line buffer becomes `x[dh:dh+H, dw:dw+W]`)
  parallel MAC   -> per-tap 32x32 fixed multiply via 16-BIT LIMB
                    DECOMPOSITION (below), int32 wraparound accumulate —
                    the DSP MAC array, one tap per unrolled step
  bias add       -> `fixed_add` (wraparound, or sign-checked saturation)
  PLAN sigmoid   -> shift-add piecewise-linear unit (optional epilogue)
  maxpool 2x2/2  -> 3-comparator tree over row/col selects (optional
                    epilogue; kernels/pooling.py)

Taps and bias arrive in SMEM and are read as scalars, then broadcast to the
tile before the limb multiply: the limb split bitcasts its operands, and
Mosaic only bitcasts vectors.

Why the limb decomposition: a Qm.n product needs the full 64-bit result of a
32x32 multiply before the >> frac_bits renormalization, but the TPU (and
JAX without x64) only has 32-bit integer lanes.  So `fixed_point
._full_mul_shift` splits each operand into an unsigned low limb (16 bits)
and a signed high limb and reassembles

    a*b = ah*bh*2^32 + (ah*bl + al*bh)*2^16 + al*bl   (mod 2^32 after >>),

where every partial product provably fits 32 bits.  The kernel body calls
the SAME `fixed_point` helpers the emulated "fixed" backend uses, so the two
substrates cannot drift: any future change to the arithmetic lands on both.

Why interpret mode is bit-identical to compiled mode: every op in the
pipeline is integer (shifts, masks, adds, compares, bitcasts) — there is no
floating-point reassociation, no MXU accumulation-order freedom, nothing
with rounding latitude.  Integer two's-complement ops have exactly one
defined result, so the Pallas interpreter on CPU and the compiled TPU kernel
produce the same words.  (The only float in sight is the documented f32
magnitude *heuristic* that drives the optional saturation decision; it is
elementwise and identically evaluated on both substrates.)

Grid: (batch,) with whole spatial dims in VMEM, mirroring kernels/conv2d;
the ops.py wrapper enforces the VMEM budget and handles padding/stride.

Granularity note: this kernel fuses ONE pipeline stage per launch (the
deployed 28x28 classifier runs two of them).  `kernels/frame_trunk` is the
whole-frame sibling: both trunk stages plus the sweep's quad role maps over
a spatially TILED big frame in a single launch, built from the same
`fixed_point` helpers — so the two fusion granularities share one
arithmetic definition and cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixed_point as fxp
from repro.kernels.pooling import pool2x2

_TAPS = ((0, 0), (0, 1), (1, 0), (1, 1))   # (dh, dw) per 2x2 kernel tap


def _fixed_conv_kernel(x_ref, w_ref, b_ref, o_ref, *,
                       cfg: fxp.FixedPointConfig, activation: str | None,
                       pool: bool):
    x = x_ref[0]                                       # (H+1, W+1) int32
    H = x.shape[0] - 1
    W = x.shape[1] - 1
    acc = jnp.zeros((H, W), jnp.int32)
    for t, (dh, dw) in enumerate(_TAPS):               # unrolled MAC taps
        win = x[dh:dh + H, dw:dw + W]                  # windowing module
        w = jnp.full((H, W), w_ref[t], jnp.int32)      # SMEM tap, broadcast
        acc = acc + fxp.fixed_mul(win, w, cfg)         # limb MAC, int32 wrap
    y = fxp.fixed_add(acc, jnp.full((H, W), b_ref[0], jnp.int32), cfg)
    if activation == "plan":
        y = fxp.fixed_sigmoid_plan(y, cfg)             # shift-add PLAN unit
    if pool:
        y = pool2x2(y)                                 # comparator tree
    o_ref[...] = y[None]


def fixed_conv2d_pallas(x: jnp.ndarray, w4: jnp.ndarray, b: jnp.ndarray, *,
                        cfg: fxp.FixedPointConfig = fxp.Q16_16,
                        activation: str | None = None, pool: bool = False,
                        interpret: bool) -> jnp.ndarray:
    """x (B, H+1, W+1) int32 pre-padded (SAME: 0 after); w4 (4,) int32 taps;
    b (1,) int32 bias word.  Returns (B, H, W) int32, or the pooled
    (B, H//2, W//2) when `pool` fuses the comparator-tree stage."""
    B, Hp, Wp = x.shape
    H, W = Hp - 1, Wp - 1
    Ho, Wo = (H // 2, W // 2) if pool else (H, W)
    kern = functools.partial(_fixed_conv_kernel, cfg=cfg,
                             activation=activation, pool=pool)
    return pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp), lambda i: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, Ho, Wo), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo), jnp.int32),
        interpret=interpret,
    )(x, w4, b)


def _fixed_pool_kernel(x_ref, o_ref):
    o_ref[...] = pool2x2(x_ref[0])[None]


def fixed_maxpool2x2_pallas(x: jnp.ndarray, *,
                            interpret: bool) -> jnp.ndarray:
    """x (B, H, W) int32, H/W even (wrapper crops) -> (B, H/2, W/2)."""
    B, H, W = x.shape
    return pl.pallas_call(
        _fixed_pool_kernel,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, W), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, H // 2, W // 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H // 2, W // 2), jnp.int32),
        interpret=interpret,
    )(x)


def _fixed_plan_kernel(x_ref, o_ref, *, cfg: fxp.FixedPointConfig):
    o_ref[...] = fxp.fixed_sigmoid_plan(x_ref[...], cfg)


def fixed_sigmoid_plan_pallas(x: jnp.ndarray, *,
                              cfg: fxp.FixedPointConfig = fxp.Q16_16,
                              block_rows: int = 256,
                              interpret: bool) -> jnp.ndarray:
    """x (R, C) int32, R a multiple of block_rows (wrapper pads) -> int32
    PLAN sigmoid words, the VPU shift-add activation unit."""
    R, C = x.shape
    return pl.pallas_call(
        functools.partial(_fixed_plan_kernel, cfg=cfg),
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.int32),
        interpret=interpret,
    )(x)
