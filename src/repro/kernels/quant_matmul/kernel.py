"""int8 x int8 -> int32 tiled matmul with fused per-channel dequant.

The TPU-native generalization of the paper's fixed-point MAC array: match
the numeric format to the native multiplier.  The Zynq DSP48 is a 25x18-bit
multiplier, hence the paper's fixed-point ints; the MXU's cheap multiplier is
int8 (2x the bf16 rate on v5e), hence int8 storage with exact int32
accumulation — same co-design insight, different optimum.

Tiling: grid (M/bm, N/bn, K/bk), K innermost so the (bm, bn) int32
accumulator stays resident in VMEM scratch across the K sweep (the MXU
analogue of the DSP accumulator register), with a fused dequant epilogue on
the last K step.  Block sizes are MXU-aligned (multiples of 8 x 128; int8
lanes pack 32x128 tiles natively).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixed_point as fxp


def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        # fused dequant: int32 accumulator * (row scale x col scale)
        o_ref[...] = (acc_ref[...].astype(jnp.float32)
                      * sx_ref[...].reshape(-1, 1) * sw_ref[...].reshape(1, -1))


def quant_matmul_pallas(xq: jnp.ndarray, wq: jnp.ndarray,
                        sx: jnp.ndarray, sw: jnp.ndarray, *,
                        bm: int = 256, bn: int = 256, bk: int = 512,
                        interpret: bool) -> jnp.ndarray:
    """xq (M,K) int8, wq (K,N) int8, sx (M,) f32 row scales, sw (N,) f32
    per-channel scales -> (M,N) f32.  M,K,N must be multiples of the block
    sizes (the ops.py wrapper pads)."""
    M, K = xq.shape
    _, N = wq.shape
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _qmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm,), lambda i, j, k: (i,)),
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, wq, sx, sw)


# ---------------------------------------------------------------------------
# Fixed-point (Qm.n) dense MAC — the int32 sibling of the int8 kernel above
# ---------------------------------------------------------------------------

def _fixed_mm_kernel(x_ref, w_ref, b_ref, o_ref, *, cfg: fxp.FixedPointConfig):
    """One batch block: the Qm.n MAC array + bias add, inside the launch.

    This CANNOT use `jnp.dot`: the Qm.n MAC renormalizes (>> frac_bits,
    wrap) EVERY product before accumulating, exactly like the paper's DSP
    array — so the kernel body calls the SAME `fixed_matmul`/`fixed_add`
    the emulated "fixed" backend uses (bit-exactness by construction).
    Every op is integer -> interpret mode is bit-identical to compiled.
    """
    y = fxp.fixed_matmul(x_ref[...], w_ref[...], cfg)          # (bm, N)
    o_ref[...] = fxp.fixed_add(y, b_ref[...].reshape(1, -1), cfg)


def fixed_matmul_pallas(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                        cfg: fxp.FixedPointConfig = fxp.Q16_16,
                        bm: int = 128, interpret: bool) -> jnp.ndarray:
    """x (M,K) int32 Qm.n, w (K,N) int32, b (N,) int32 -> (M,N) int32.
    M must be a multiple of bm (the ops.py wrapper pads); K and N stay whole
    so the per-row MAC sweep lives in one program instance."""
    M, K = x.shape
    _, N = w.shape
    return pl.pallas_call(
        functools.partial(_fixed_mm_kernel, cfg=cfg),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            pl.BlockSpec((K, N), lambda i: (0, 0)),
            pl.BlockSpec((N,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(x, w, b)
