"""jit'd wrapper of the multi-channel fixed-point conv: SAME padding, the
im2col patch rows, block sizes from the memory budgets, and the NHWC
words back.

    fixed_conv_mc(x, w, b, stride=s, cfg=...)   (B,H,W,Cin) -> (B,Ho,Wo,Cout)

TensorFlow SAME padding: the output is ceil(H / s); the total padding
max((Ho - 1) s + k - H, 0) is split with the smaller half before, so a 3x3
stride-2 conv on an even extent pads 0 before and 1 after, and a 1x1
stride-2 conv pads nothing.

The kernel launch is `_fixed_conv_mc_jit`, a jitted function that holds
nothing but the `pallas_call`, so the device trace names the kernel's op
after it (the patch rows and the layout changes around it are ops of
their own).

Block sizes (`choose_blocks`) come from three budgets, none a fixed block:

  * bk, the reduction rows per grid step: the weights of a K block sit in
    SMEM as scalars, double-buffered, within `_SMEM_WORDS` a buffer; all
    of K when it fits, else the block of whole SMEM tiles that pads K
    least, the largest of those (a zero patch row adds 0);
  * bs, the pixel rows of 128 per block: the accumulator carried through
    the reduction loop and the limb temporaries of one fixed multiply are
    live together, bs/8 vregs each, within the 64-vreg register file;
  * VMEM: the double-buffered patch and output blocks plus the resident
    accumulator must fit `_VMEM_BUDGET`: bs shrinks, then bk, until they
    do (explicit `blocks` that do not fit raise).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import fixed_point as fxp
from repro.core import runtime
from repro.kernels.fixed_conv_mc.kernel import fixed_conv_mc_pallas

LANES = 128
_VMEM_BUDGET = 14 * 2 ** 20   # leave headroom out of ~16 MB/core
_SMEM_WORDS = 8192            # one buffer of the K block's weight scalars
_SMEM_TILE = 1024             # a 1-D SMEM block is a multiple of this
_VREGS = 64                   # vector registers of one TensorCore
_LIVE_PER_ROWBLOCK = 8        # accumulator + ~7 limb temporaries, per 8 rows


def same_padding(size: int, k: int, stride: int) -> tuple[int, int, int]:
    """TensorFlow SAME: (output extent, pad before, pad after)."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return out, total // 2, total - total // 2


def im2col(x: jnp.ndarray, kh: int, kw: int,
           stride: int) -> tuple[jnp.ndarray, tuple[int, int, int]]:
    """(B,H,W,C) words -> (kh*kw*C, B*Ho*Wo) patch rows, row index
    (dy*kw + dx)*C + c (the HWIO weight's row-major (K, N) view), column
    index (b*Ho + oy)*Wo + ox."""
    B, H, W, C = x.shape
    Ho, ph0, ph1 = same_padding(H, kh, stride)
    Wo, pw0, pw1 = same_padding(W, kw, stride)
    xp = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    taps = [xp[:, dy:dy + (Ho - 1) * stride + 1:stride,
               dx:dx + (Wo - 1) * stride + 1:stride, :]
            for dy in range(kh) for dx in range(kw)]
    cols = jnp.stack(taps)                             # (T, B, Ho, Wo, C)
    cols = cols.transpose(0, 4, 1, 2, 3)               # (T, C, B, Ho, Wo)
    return cols.reshape(kh * kw * C, B * Ho * Wo), (B, Ho, Wo)


def choose_blocks(K: int, M: int, N: int) -> tuple[int, int]:
    """(bk, bs) for a (K, M) reduction into N channels (module docstring)."""
    step = _SMEM_TILE // math.gcd(N, _SMEM_TILE)        # bk*N tiles SMEM
    tiled = sorted(range(step, _SMEM_WORDS // N + 1, step),
                   key=lambda d: (-(-K // d) * d, -d))  # least padding
    rows = -(-M // LANES)
    cap = min(8 * (_VREGS // _LIVE_PER_ROWBLOCK), -(-rows // 8) * 8)
    for bk in ([K] if K * N <= _SMEM_WORDS else []) + tiled:
        for bs in range(cap, 0, -8):
            if vmem_bytes(bk, bs, N) <= _VMEM_BUDGET:
                return bk, bs
    raise ValueError(f"no fixed_conv_mc blocks fit VMEM for K={K}, N={N}")


def vmem_bytes(bk: int, bs: int, N: int) -> int:
    """Double-buffered patch and output blocks plus the accumulator."""
    return (2 * bk * bs + 3 * N * bs) * LANES * 4


def fixed_conv_mc(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                  stride: int = 1,
                  cfg: fxp.FixedPointConfig = fxp.Q16_16,
                  blocks: tuple[int, int] | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Fixed-point k x k SAME conv over channels: x (B,H,W,Cin) int32
    words, w (kh,kw,Cin,Cout) int32 HWIO, b (Cout,) int32 -> (B,Ho,Wo,Cout)
    int32, word-exact with `fixed_point.fixed_matmul` + `fixed_add` over
    the same patch rows.  `blocks=(bk, bs)` overrides `choose_blocks`
    (tests use it to tile the reduction); `interpret=None` follows the
    `core.runtime` process default."""
    kh, kw, cin, N = w.shape
    cols, (B, Ho, Wo) = im2col(x.astype(jnp.int32), kh, kw, stride)
    K, M = cols.shape
    bk, bs = blocks if blocks is not None else choose_blocks(K, M, N)
    vmem = vmem_bytes(bk, bs, N)
    if vmem > _VMEM_BUDGET:
        raise ValueError(f"fixed_conv_mc blocks exceed the VMEM budget: "
                         f"{vmem} B (bk={bk}, bs={bs}, N={N})")
    Kp = -(-K // bk) * bk
    Rp = -(-M // (bs * LANES)) * bs
    cols = jnp.pad(cols, ((0, Kp - K), (0, Rp * LANES - M)))
    wk = jnp.pad(w.astype(jnp.int32).reshape(K, N), ((0, Kp - K), (0, 0)))
    y = _fixed_conv_mc_jit(cols.reshape(Kp, Rp, LANES), wk.reshape(-1),
                           b.reshape(N).astype(jnp.int32), cfg=cfg, bk=bk,
                           bs=bs,
                           interpret=runtime.resolve_interpret(interpret))
    y = y.reshape(N, Rp * LANES)[:, :M].reshape(N, B, Ho, Wo)
    return y.transpose(1, 2, 3, 0)


@functools.partial(jax.jit, static_argnames=("cfg", "bk", "bs",
                                             "interpret"))
def _fixed_conv_mc_jit(cols: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                       *, cfg: fxp.FixedPointConfig, bk: int, bs: int,
                       interpret: bool) -> jnp.ndarray:
    return fixed_conv_mc_pallas(cols, w, b, cfg=cfg, bk=bk, bs=bs,
                                interpret=interpret)
