"""PLAN piecewise-linear sigmoid as a Pallas VPU kernel.

The paper's activation block is a logic-level sigmoid; in hardware the
standard realization is the PLAN approximation (shift-add only).  On TPU
this is a VPU (vector unit) elementwise kernel: selects + multiply-adds on
(8,128)-aligned VMEM tiles — included both as the activation epilogue used
by the fixed-point serving path and as the minimal example of a VPU-only
Pallas kernel in this codebase.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _plan_kernel(x_ref, o_ref):
    x = x_ref[...]
    ax = jnp.abs(x)
    y = jnp.where(ax >= 5.0, 1.0,
                  jnp.where(ax >= 2.375, 0.03125 * ax + 0.84375,
                            jnp.where(ax >= 1.0, 0.125 * ax + 0.625,
                                      0.25 * ax + 0.5)))
    o_ref[...] = jnp.where(x < 0, 1.0 - y, y)


def sigmoid_pla_pallas(x: jnp.ndarray, *, block_rows: int = 256,
                       interpret: bool) -> jnp.ndarray:
    """x (R, C) f32, R a multiple of block_rows (wrapper pads)."""
    R, C = x.shape
    return pl.pallas_call(
        _plan_kernel,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=interpret,
    )(x)
