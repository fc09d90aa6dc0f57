"""2x2/2 max pooling as a Pallas kernel — the paper's comparator-tree block.

One program instance pools one (image, channel) map; the 2x2 window is
realized as a 3-comparator tree over row/column selects of the map
(kernels/pooling.py) — exactly the FPGA structure, but vectorized over the
whole feature map on the VPU.  Channels move in front of the spatial dims
outside the kernel, so each block is a 2D map with W on the lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pooling import pool2x2


def _pool_kernel(x_ref, o_ref):
    o_ref[...] = pool2x2(x_ref[0])[None]


def maxpool2d_pallas(x: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """x (B, H, W, C) with H, W even -> (B, H/2, W/2, C)."""
    B, H, W, C = x.shape
    maps = jnp.moveaxis(x, 3, 1).reshape(B * C, H, W)
    y = pl.pallas_call(
        _pool_kernel,
        grid=(B * C,),
        in_specs=[pl.BlockSpec((1, H, W), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, H // 2, W // 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * C, H // 2, W // 2), x.dtype),
        interpret=interpret,
    )(maps)
    return jnp.moveaxis(y.reshape(B, C, H // 2, W // 2), 1, 3)
