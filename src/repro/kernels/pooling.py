"""2x2/2 pooling selects that Mosaic can lower, shared by the pool kernels.

The obvious kernel spelling of a 2x2/2 pool, strided value slices
(`y[::2, ::2]`), lowers to `lax.gather`, which the TPU compiler rejects for
these shapes; strided ref loads are no way out either, because Mosaic
refuses a stride on the lane (last) dim.  So rows are picked by splitting
the sublane dim (`(h, w) -> (h/2, 2, w)`, then index the pair slot) and
columns by the same split on the transpose.  Pure selects and compares:
exact in every dtype, and identical in interpret mode and compiled.
"""
from __future__ import annotations

import jax.numpy as jnp


def _rows(x: jnp.ndarray, parity: int) -> jnp.ndarray:
    """Every other row of a 2D (h, w) map, h even, starting at `parity`."""
    h, w = x.shape
    return x.reshape(h // 2, 2, w)[:, parity, :]


def _cols(x: jnp.ndarray, parity: int) -> jnp.ndarray:
    """Every other column of a 2D (h, w) map, w even, starting at `parity`."""
    return _rows(x.T, parity).T


def pool_quadrants(tl, tr, bl, br) -> jnp.ndarray:
    """2x2/2 max pool of 2D maps with one source per window quadrant:
    (2r, 2c) from tl, (2r, 2c+1) from tr, (2r+1, 2c) from bl, (2r+1, 2c+1)
    from br.  All four maps share an even (h, w) shape."""
    return jnp.maximum(
        jnp.maximum(_cols(_rows(tl, 0), 0), _cols(_rows(tr, 0), 1)),
        jnp.maximum(_cols(_rows(bl, 1), 0), _cols(_rows(br, 1), 1)))


def pool_mix(e, o) -> jnp.ndarray:
    """2x2/2 max pool whose even input rows come from `e` and odd rows
    from `o`."""
    return pool_quadrants(e, e, o, o)


def pool2x2(y: jnp.ndarray) -> jnp.ndarray:
    """Plain VALID 2x2/2 max pool of a 2D map (odd extents cropped): the
    3-comparator tree."""
    h, w = y.shape
    y = y[:h - h % 2, :w - w % 2]
    r = jnp.maximum(_rows(y, 0), _rows(y, 1))
    return jnp.maximum(_cols(r, 0), _cols(r, 1))
