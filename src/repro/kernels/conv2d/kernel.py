"""Windowed conv as a Pallas TPU kernel — the paper's conv datapath on the MXU.

The FPGA design (paper Fig. 4) = windowing module -> parallel MAC array ->
bias -> activation, sequenced by an FSM.  TPU-native mapping:

  windowing module  -> static shifted VMEM views (the line buffer becomes
                       `x_ref[dh:dh+H, dw:dw+W]` slices of the padded block)
  parallel MAC array-> one MXU `jnp.dot` per kernel tap: (H*W, Cin)@(Cin, Cout),
                       accumulated in f32 — KH*KW taps unrolled, exactly the
                       paper's "one MAC per tap" parallelism but systolic
  BRAM feature maps -> VMEM blocks, double-buffered by the Pallas grid
                       pipeline (the grid schedule is the FSM)
  bias + activation -> fused epilogue in the same kernel; `activation` picks
                       the exact sigmoid or the PLAN piecewise-linear unit
                       (the paper's shift-add hardware sigmoid), so the
                       conv+PLAN fast path is a single kernel launch

Grid: (batch,) — each program instance convolves one image; spatial dims are
kept whole in VMEM (checked by the wrapper against the VMEM budget).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fixed_point import sigmoid_plan_f32

_ACTIVATIONS = (None, "sigmoid", "plan")
_F32 = jax.lax.Precision.HIGHEST    # full-f32 MACs, like the XLA reference


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, kw: int,
                 stride: int, activation: str | None):
    Hs, Ws, cout = o_ref.shape[1], o_ref.shape[2], o_ref.shape[3]
    cin = x_ref.shape[3]
    # kept-pixel spans: output (i,j) reads input (i*stride+dh, j*stride+dw),
    # so each tap loads a contiguous window and keeps every stride-th row/col
    # BEFORE the MXU dot — the accumulator and the MAC work cover only the
    # strided output, never the full stride-1 grid.
    hspan, wspan = (Hs - 1) * stride + 1, (Ws - 1) * stride + 1
    acc = jnp.zeros((Hs * Ws, cout), jnp.float32)
    for dh in range(kh):            # static unroll: the parallel MAC taps
        for dw in range(kw):
            win = x_ref[0, dh:dh + hspan, dw:dw + wspan, :]  # windowing
            win = win[::stride, ::stride]                    # kept rows/cols
            acc = acc + jnp.dot(win.reshape(Hs * Ws, cin), w_ref[dh, dw],
                                precision=_F32,
                                preferred_element_type=jnp.float32)
    acc = acc + b_ref[...]                                    # bias add
    if activation == "sigmoid":                               # activation unit
        acc = jax.nn.sigmoid(acc)
    elif activation == "plan":
        acc = sigmoid_plan_f32(acc)
    o_ref[...] = acc.reshape(1, Hs, Ws, cout)


def conv2d_pallas(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                  stride: int = 1,
                  apply_sigmoid: bool = False,
                  activation: str | None = None,
                  interpret: bool) -> jnp.ndarray:
    """x (B, H+kh-1, W+kw-1, Cin) pre-padded; w (kh, kw, Cin, Cout); b (Cout,).
    Returns (B, ceil(H/stride), ceil(W/stride), Cout) f32 — stride is realized
    NATIVELY: only the kept rows/columns are MAC'd and only the strided output
    block lives in VMEM.  `activation` in {None, "sigmoid", "plan"} selects
    the fused epilogue (`apply_sigmoid=True` is legacy spelling for
    "sigmoid")."""
    if activation is None and apply_sigmoid:
        activation = "sigmoid"
    if activation not in _ACTIVATIONS:
        raise ValueError(f"activation must be one of {_ACTIVATIONS}")
    B, Hp, Wp, cin = x.shape
    kh, kw, _, cout = w.shape
    H1, W1 = Hp - kh + 1, Wp - kw + 1
    Hs, Ws = -(-H1 // stride), -(-W1 // stride)   # kept rows/cols (ceil)
    kern = functools.partial(_conv_kernel, kh=kh, kw=kw, stride=stride,
                             activation=activation)
    return pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hp, Wp, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((kh, kw, cin, cout), lambda i: (0, 0, 0, 0)),
            pl.BlockSpec((cout,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((1, Hs, Ws, cout), lambda i: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hs, Ws, cout), jnp.float32),
        interpret=interpret,
    )(x, w, b)
