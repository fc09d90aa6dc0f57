"""Multi-channel Qm.n fixed-point conv as a Pallas kernel: the MAC array
over C_in x k x k reductions and C_out channels, entirely in int32.

The conv arrives as a (K, M) reduction: K = k*k*C_in patch words per output
pixel (the im2col rows, built by the ops.py wrapper), M output pixels of
the whole batch.  The kernel computes, for every output channel n,

    acc[n, m] = sum_k  fixed_mul(x[k, m], w[k, n])      (int32 wraparound)
    out[n, m] = fixed_add(wrap(acc[n, m]), b[n])

— exactly `fixed_point.fixed_matmul` + `fixed_add`, the MAC-array contract
of the emulated "fixed" backend: every product is renormalized (>> frac,
rounded, wrapped) on its own before it is accumulated.

Layout.  M lies on the (sublane, lane) tile: the patch words arrive as
(K, M/128, 128) and one reduction row x[k] is a (bs, 128) block of output
pixels, so every vector op is lane-dense whatever C_out is (16 output
channels would fill 16 of 128 lanes in an (M, N) layout).  The weight
w[k, n] is one SMEM scalar, splat across the block.  The output is
(C_out, M/128, 128): channel-major, lane-dense.

Grid (M blocks, K blocks): K is the innermost axis and the int32
accumulator (C_out, bs, 128) stays resident in VMEM scratch across it.
Int32 wraparound addition is associative, so a K-tiled accumulation gives
the same words as one pass over K.  The epilogue (wrap to the word width,
bias add) runs on the last K step.

Why interpret mode is bit-identical to compiled mode: every op is integer
(see kernels/fixed_conv/kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fixed_point as fxp


def _fixed_conv_mc_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *,
                          cfg: fxp.FixedPointConfig, bk: int, n_out: int):
    kstep = pl.program_id(1)

    @pl.when(kstep == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def channel(n, carry):
        def tap(j, acc):
            w = jnp.full(acc.shape, w_ref[j * n_out + n], jnp.int32)
            return acc + fxp.fixed_mul(x_ref[j], w, cfg)   # limb MAC
        acc_ref[n] = jax.lax.fori_loop(0, bk, tap, acc_ref[n])
        return carry

    jax.lax.fori_loop(0, n_out, channel, 0)

    @pl.when(kstep == pl.num_programs(1) - 1)
    def _epilogue():
        def bias(n, carry):
            y = fxp._wrap_to_bits(acc_ref[n], cfg.total_bits)
            o_ref[n] = fxp.fixed_add(
                y, jnp.full(y.shape, b_ref[n], jnp.int32), cfg)
            return carry
        jax.lax.fori_loop(0, n_out, bias, 0)


def fixed_conv_mc_pallas(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                         cfg: fxp.FixedPointConfig, bk: int, bs: int,
                         interpret: bool) -> jnp.ndarray:
    """x (K, R, 128) int32 patch words; w (K * N,) int32, row-major (K, N);
    b (N,) int32.  K must be a multiple of bk and R of bs (the ops.py
    wrapper pads).  Returns (N, R, 128) int32."""
    K, R, L = x.shape
    N = b.shape[0]
    kern = functools.partial(_fixed_conv_mc_kernel, cfg=cfg, bk=bk,
                             n_out=N)
    return pl.pallas_call(
        kern,
        grid=(R // bs, K // bk),
        in_specs=[
            pl.BlockSpec((bk, bs, L), lambda i, k: (k, i, 0)),
            pl.BlockSpec((bk * N,), lambda i, k: (k,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((N, bs, L), lambda i, k: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, R, L), jnp.int32),
        scratch_shapes=[pltpu.VMEM((N, bs, L), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, b)
