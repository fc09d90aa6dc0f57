"""ResNet-8 — MLPerf Tiny's image-classification model over the backends.

Source: "ResNet-V1 (8 layers)", Banbury et al., MLPerf Tiny Benchmark,
arXiv:2106.07597; reference code mlcommons/tiny,
benchmark/training/image_classification/keras_model.py (resnet_v1_eembc).
Input 32x32x3; every conv TensorFlow SAME:

    stem     conv 3x3, 16 -> BN -> ReLU
    stack 1  [conv 3x3, 16 -> BN -> ReLU -> conv 3x3, 16 -> BN]
             + identity -> ReLU
    stack 2  [conv 3x3 /2, 32 -> BN -> ReLU -> conv 3x3, 32 -> BN]
             + conv 1x1 /2, 32 -> ReLU
    stack 3  the same with 64 channels
    head     average pool 8x8 -> dense 64 -> 10 (-> softmax)

77,706 weights and biases after folding; 12,501,632 multiply-accumulates
per image.

Departures from the published model, each what a fixed-point deployment
does:

  * BN folded: every BN is folded into the conv before it, in float, before
    quantizing (w * g / sqrt(v + eps), (b - m) * g / sqrt(v + eps) + beta),
    so `params` hold one (w, b) per conv (`fold_bn`).
  * No softmax: the output is the dense layer's logits (words on the fixed
    backends); softmax is monotone and does not change the Max Finder's
    class.
  * On the fixed backends every product is rounded and wrapped to the word
    on its own before it is accumulated (the datapath's MAC array), and
    sums wrap.
  * The average pool is the int32 sum of the 64 words of a channel and a
    rounding right shift by 6 (`backends.global_avgpool_fixed`).

The graph lives once, in `apply(params, images, backend=...)`, on the
backend primitives `ingest_channels`, `conv`, `relu`, `accumulate` (the
residual add), `global_avgpool` and `dense`. Each stage runs under a
`jax.named_scope` (`resnet8.stem`, `resnet8.stack1` .. `resnet8.head`).
`forward_ref` is the plain float32 reference: `jax.numpy` only, no kernel
and no backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import backends as B

IMAGE_SHAPE = (32, 32, 3)
N_CLASSES = 10
BN_EPS = 1e-3                 # Keras BatchNormalization's default

# conv name -> (kernel, in channels, out channels, stride)
CONVS = {
    "stem": (3, 3, 16, 1),
    "s1a": (3, 16, 16, 1), "s1b": (3, 16, 16, 1),
    "s2a": (3, 16, 32, 2), "s2b": (3, 32, 32, 1), "s2p": (1, 16, 32, 2),
    "s3a": (3, 32, 64, 2), "s3b": (3, 64, 64, 1), "s3p": (1, 32, 64, 2),
}
DENSE = (64, N_CLASSES)
# the convs followed by BN; the 1x1 projection shortcuts are not
BN_CONVS = ("stem", "s1a", "s1b", "s2a", "s2b", "s3a", "s3b")


def fold_bn(w, b, gamma, beta, mean, var, eps: float = BN_EPS):
    """Fold an inference BN into the conv before it: (w', b')."""
    s = gamma / jnp.sqrt(var + eps)
    return w * s, (b - mean) * s + beta


def init_params(key: jax.Array) -> dict:
    """Seeded folded parameters: He-normal conv weights, zero conv biases
    (the Keras defaults), then a BN of random statistics folded in
    (gamma in [0.5, 1], beta ~ N(0, 0.1), mean ~ N(0, 0.1), var in
    [0.5, 1.5]). The BN scale keeps each block's output near its input's
    size, so the residual sums stay far inside Q16.16's +-32768."""
    keys = iter(jax.random.split(key, 5 * len(CONVS) + 1))
    params = {}
    for name, (k, cin, cout, _) in CONVS.items():
        w = jax.random.normal(next(keys), (k, k, cin, cout), jnp.float32)
        w = w * jnp.sqrt(2.0 / (k * k * cin))
        b = jnp.zeros((cout,), jnp.float32)
        if name in BN_CONVS:
            w, b = fold_bn(
                w, b,
                jax.random.uniform(next(keys), (cout,), jnp.float32, 0.5, 1.0),
                0.1 * jax.random.normal(next(keys), (cout,), jnp.float32),
                0.1 * jax.random.normal(next(keys), (cout,), jnp.float32),
                jax.random.uniform(next(keys), (cout,), jnp.float32, 0.5, 1.5))
        params[name] = {"w": w, "b": b}
    cin, cout = DENSE
    wd = jax.random.normal(next(keys), (cin, cout), jnp.float32)
    params["dense"] = {"w": wd * jnp.sqrt(2.0 / cin),
                       "b": jnp.zeros((cout,), jnp.float32)}
    return params


def param_count(params: dict) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def _stage(be, p, x, a, b, proj=None, stride=1):
    """[conv a -> ReLU -> conv b] + shortcut -> ReLU; the shortcut is the
    identity, or the 1x1 projection `proj` at the block's stride."""
    y = be.relu(be.conv(x, p[a]["w"], p[a]["b"], stride))
    y = be.conv(y, p[b]["w"], p[b]["b"], 1)
    s = x if proj is None else be.conv(x, p[proj]["w"], p[proj]["b"], stride)
    return be.relu(be.accumulate(s, y))


def apply(params: dict, images: jnp.ndarray, *,
          backend: str | B.Backend = "ref") -> jnp.ndarray:
    """images (B,32,32,3) float in [0, 1] -> logits (B,10): float on the
    float backends, Qm.n int32 words on the fixed ones. `params` may be
    float (quantized on the way in) or backend-native."""
    be = B.get_backend(backend)
    p = be.prepare_params(params)
    with jax.named_scope("resnet8.stem"):
        x = be.ingest_channels(images)
        x = be.relu(be.conv(x, p["stem"]["w"], p["stem"]["b"], 1))
    with jax.named_scope("resnet8.stack1"):
        x = _stage(be, p, x, "s1a", "s1b")
    with jax.named_scope("resnet8.stack2"):
        x = _stage(be, p, x, "s2a", "s2b", "s2p", 2)
    with jax.named_scope("resnet8.stack3"):
        x = _stage(be, p, x, "s3a", "s3b", "s3p", 2)
    with jax.named_scope("resnet8.head"):
        x = be.global_avgpool(x)
        return be.dense(x, p["dense"]["w"], p["dense"]["b"])


def predict(scores: jnp.ndarray) -> jnp.ndarray:
    """The Max Finder: argmax over logits or logit words."""
    return jnp.argmax(scores, axis=-1)


# ---------------------------------------------------------------------------
# plain float32 reference
# ---------------------------------------------------------------------------

def _conv_ref(x, w, b, stride):
    """TensorFlow SAME conv as a sum over taps of channel contractions."""
    k = w.shape[0]
    H, W = x.shape[1:3]
    Ho, Wo = -(-H // stride), -(-W // stride)
    ph = max((Ho - 1) * stride + k - H, 0)
    pw = max((Wo - 1) * stride + k - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)))
    y = b
    for dy in range(k):
        for dx in range(k):
            win = xp[:, dy:dy + (Ho - 1) * stride + 1:stride,
                     dx:dx + (Wo - 1) * stride + 1:stride, :]
            y = y + jnp.einsum("bhwc,cn->bhwn", win, w[dy, dx])
    return y


def forward_ref(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """The published forward pass (BN folded, no softmax) in float32."""
    relu = jax.nn.relu
    p = params
    with jax.default_matmul_precision("highest"):
        x = relu(_conv_ref(images, p["stem"]["w"], p["stem"]["b"], 1))
        for a, b, proj, s in (("s1a", "s1b", None, 1),
                              ("s2a", "s2b", "s2p", 2),
                              ("s3a", "s3b", "s3p", 2)):
            y = relu(_conv_ref(x, p[a]["w"], p[a]["b"], s))
            y = _conv_ref(y, p[b]["w"], p[b]["b"], 1)
            sc = x if proj is None else _conv_ref(x, p[proj]["w"],
                                                   p[proj]["b"], s)
            x = relu(sc + y)
        x = jnp.mean(x, axis=(1, 2))
        return x @ p["dense"]["w"] + p["dense"]["b"]
