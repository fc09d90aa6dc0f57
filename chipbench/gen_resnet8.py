"""The benchmark's generator for ResNet-8: BN-folded weights and 32x32x3
images, pure functions of the seed (numpy `default_rng` keyed by the seed
and a role tag, as in `chipbench/gen.py`). Nothing here imports the
program.

Weights follow `resnet_v1_eembc`'s initialisation (He-normal convs and
dense layer, zero biases) with a BatchNormalization of random statistics
folded into each conv that has one: gamma in [0.5, 1], beta and the
running mean ~ N(0, 0.1), the running variance in [0.5, 1.5], eps 1e-3.
That scale keeps every block's output near the size of its input, so the
residual sums stay far inside Q16.16's +-32768 and the words are the
network's, not wrapped garbage.

Images are CIFAR-sized crops in [0, 1]: a smooth colour field (a 4x4x3
grid of uniform levels, upsampled) under pixel noise.
"""
from __future__ import annotations

import numpy as np

from chipbench.gen import rng_for

SIDE = 32
# conv name -> (kernel, in channels, out channels, stride): resnet_v1_eembc
CONVS = {
    "stem": (3, 3, 16, 1),
    "s1a": (3, 16, 16, 1), "s1b": (3, 16, 16, 1),
    "s2a": (3, 16, 32, 2), "s2b": (3, 32, 32, 1), "s2p": (1, 16, 32, 2),
    "s3a": (3, 32, 64, 2), "s3b": (3, 64, 64, 1), "s3p": (1, 32, 64, 2),
}
PROJECTIONS = ("s2p", "s3p")          # 1x1 shortcuts carry no BN
N_CLASSES = 10
EPS = 1e-3


def params(seed: int, convs: dict | None = None) -> dict:
    """Folded float32 parameters: {conv: {"w": HWIO, "b": (Cout,)},
    "dense": {"w": (64, 10), "b": (10,)}}. `convs` replaces the published
    widths (the tests' narrower network)."""
    convs = convs or CONVS
    rng = rng_for(seed, 0x4E58)
    out = {}
    for name, (k, cin, cout, _) in convs.items():
        w = rng.standard_normal((k, k, cin, cout)) * np.sqrt(2.0 / (k * k
                                                                  * cin))
        b = np.zeros(cout)
        if name not in PROJECTIONS:
            gamma = rng.uniform(0.5, 1.0, cout)
            beta = rng.normal(0.0, 0.1, cout)
            mean = rng.normal(0.0, 0.1, cout)
            var = rng.uniform(0.5, 1.5, cout)
            s = gamma / np.sqrt(var + EPS)
            w, b = w * s, (b - mean) * s + beta
        out[name] = {"w": w.astype(np.float32), "b": b.astype(np.float32)}
    cin = convs["s3b"][2]
    out["dense"] = {
        "w": (rng.standard_normal((cin, N_CLASSES)) * np.sqrt(2.0 / cin)
              ).astype(np.float32),
        "b": np.zeros(N_CLASSES, np.float32)}
    return out


def images(seed: int, n: int, side: int = SIDE) -> np.ndarray:
    """(n, side, side, 3) float32 images in [0, 1]."""
    rng = rng_for(seed, 0x1A6F)
    coarse = rng.uniform(0.0, 1.0, (n, 4, 4, 3))
    field = np.repeat(np.repeat(coarse, side // 4, axis=1), side // 4,
                      axis=2)
    x = field + rng.normal(0.0, 0.1, (n, side, side, 3))
    return np.clip(x, 0.0, 1.0).astype(np.float32)
