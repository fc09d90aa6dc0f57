"""ResNet-8's operations and bytes per layer, counted from its shapes.

Independent of any implementation (an im2col matrix, padded blocks or a
fused kernel count the same): 2 operations per multiply-accumulate, 1 per
bias add, residual add and pool add; 4-byte words. A conv layer reads its
input activation words once and writes its output words once per image,
and reads its weights and biases once per call.

    stem                 32x32x3  -> 32x32x16   3x3
    s1a, s1b             32x32x16 -> 32x32x16   3x3
    s2a                  32x32x16 -> 16x16x32   3x3 /2
    s2b                  16x16x32 -> 16x16x32   3x3
    s2p                  32x32x16 -> 16x16x32   1x1 /2
    s3a, s3b, s3p        the same, 16x16x32 -> 8x8x64
    head                 8x8x64 -> 64 (sum, shift), 64 -> 10 dense
"""
from __future__ import annotations

WORD = 4
# conv name -> (input side, kernel, in channels, out channels, stride)
CONVS = {
    "stem": (32, 3, 3, 16, 1),
    "s1a": (32, 3, 16, 16, 1), "s1b": (32, 3, 16, 16, 1),
    "s2a": (32, 3, 16, 32, 2), "s2b": (16, 3, 32, 32, 1),
    "s2p": (32, 1, 16, 32, 2),
    "s3a": (16, 3, 32, 64, 2), "s3b": (8, 3, 64, 64, 1),
    "s3p": (16, 1, 32, 64, 2),
}
DENSE = (64, 10)
POOL = (8, 8, 64)
# the residual adds: one per stack, at its output shape
RESIDUALS = ((32, 32, 16), (16, 16, 32), (8, 8, 64))


def conv_layer(name: str, n_images: int) -> tuple[int, int]:
    """(operations, bytes) of one conv layer over `n_images` images."""
    side, k, cin, cout, stride = CONVS[name]
    out = -(-side // stride)
    pixels = out * out
    ops = (2 * k * k * cin + 1) * cout * pixels * n_images
    nbytes = ((side * side * cin + pixels * cout) * n_images
              + k * k * cin * cout + cout) * WORD
    return ops, nbytes


def convs(n_images: int) -> tuple[int, int]:
    """(operations, bytes) of all nine conv layers."""
    parts = [conv_layer(name, n_images) for name in CONVS]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def macs_per_image() -> int:
    conv = sum(k * k * cin * cout * (-(-side // stride)) ** 2
               for side, k, cin, cout, stride in CONVS.values())
    return conv + DENSE[0] * DENSE[1]


def model_ops_per_image() -> int:
    """Every operation of one image's forward pass: the convs with their
    biases, the residual adds, the pool's sums and the dense layer."""
    residual = sum(h * w * c for h, w, c in RESIDUALS)
    h, w, c = POOL
    pool = h * w * c
    dense = (2 * DENSE[0] + 1) * DENSE[1]
    return convs(1)[0] + residual + pool + dense
