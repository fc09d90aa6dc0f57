"""smallNet — the paper's model over swappable inference backends.

Architecture (paper §III-A, Fig. 2):
    conv 1 filter 2x2, stride 1, SAME, sigmoid
    maxpool 2x2
    conv 1 filter 2x2, SAME, sigmoid
    maxpool 2x2
    flatten (7*7 = 49)
    dense 10, sigmoid
    Max Finder (argmax)
Parameter count: (2*2*1*1 + 1) * 2 + 49*10 + 10 = 510 — matches the paper's
"no more than 510 trainable parameters".

The network graph lives ONCE in `apply(params, images, backend=...)`; a
backend (core/backends.py) supplies the layer primitives.  Registered
backends: "ref" (float32, the Keras counterpart), "plan" (float32 + PLAN
hardware sigmoid), "pallas" / "pallas_plan" (the Pallas TPU kernels with
fused conv epilogues), "fixed" (bit-faithful Qm.n two's-complement — exactly
the paper's Verilog datapath, §III-B Fig. 4), "fixed_pallas" (the same Qm.n
words as ONE fused Pallas launch per pipeline stage, int32 bit-exact with
"fixed"), "int8" (TPU-native PTQ with the quant_matmul MXU kernel).

`forward` / `forward_plan` / `forward_fixed` / `forward_int8` remain as thin
wrappers over `apply` for existing callers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import ptq
from repro.distributed import sharding as shd

IMAGE_SHAPE = (28, 28, 1)


def init_params(key: jax.Array) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    glorot = jax.nn.initializers.glorot_uniform()
    return {
        "conv1": {"w": glorot(k1, (2, 2, 1, 1), jnp.float32), "b": jnp.zeros((1,), jnp.float32)},
        "conv2": {"w": glorot(k2, (2, 2, 1, 1), jnp.float32), "b": jnp.zeros((1,), jnp.float32)},
        "dense": {"w": glorot(k3, (49, 10), jnp.float32), "b": jnp.zeros((10,), jnp.float32)},
    }


def param_count(params: dict) -> int:
    return sum(int(p.size) for p in jax.tree_util.tree_leaves(params))


def seeded_params(seed: int = 0, noise: float = 0.1) -> dict:
    """Deterministic params with every leaf nonzero (`init_params` zeroes
    the biases, which would flatten any confidence landscape): the
    no-training stand-in shared by the backend parity tests, the golden
    generators, and the frozen-clip test batteries — ONE definition, so a
    recipe tweak cannot silently desynchronize what those gates pin."""
    params = init_params(jax.random.key(seed))
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    return jax.tree_util.tree_unflatten(treedef, [
        l + noise * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])


def params_from_words(words: dict,
                      cfg: fxp.FixedPointConfig = fxp.Q16_16) -> dict:
    """Float params that quantize back to exactly `words` (a Qm.n int
    pytree whose leaves are arrays or nested lists, e.g. the golden inputs
    stored in tests/golden/): each leaf is words / 2**frac_bits, exact in
    float32 for |word| < 2**24."""
    return jax.tree_util.tree_map(
        lambda w: fxp.from_fixed(jnp.asarray(w, jnp.int32), cfg), words,
        is_leaf=lambda x: isinstance(x, list))


def _constrain_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Pin dim 0 to the "batch" logical axis, replicate the rest.

    Activations change rank across backends ((B,H,W,C) float NHWC,
    (B,H,W) fixed-point words, (B,F) after flatten), so the spec is built
    from the rank.  Outside a `sharding_rules` context this is a no-op —
    the unsharded single-device path is byte-identical to before.
    """
    return shd.constrain(x, "batch", *(None,) * (x.ndim - 1))


def _conv_stages(be: B.Backend, p: dict, images: jnp.ndarray) -> jnp.ndarray:
    """Ingest + both conv->act->pool stages: images -> pooled feature maps
    ((B,7,7,1) float NHWC or (B,7,7) fixed words for 28x28 inputs; any
    spatial extent divides through as H/4 x W/4)."""
    x = _constrain_batch(be.ingest(images))
    # conv+act+pool goes through one hook so backends with a fully fused
    # stage (fixed_pallas: windowing+MAC+bias+PLAN+maxpool in ONE Pallas
    # launch) keep the paper's pipeline structure; the default composes
    # fused_conv_act and maxpool2x2 exactly as before.
    x = _constrain_batch(be.fused_conv_act_pool(x, p["conv1"]["w"], p["conv1"]["b"]))
    x = _constrain_batch(be.fused_conv_act_pool(x, p["conv2"]["w"], p["conv2"]["b"]))
    return x


def _dense_preact(be: B.Backend, p: dict, feats: jnp.ndarray) -> jnp.ndarray:
    """Pooled feature maps -> PRE-activation class scores (B, 10)."""
    x = be.flatten(feats)                                # (B, 49)
    return be.dense(x, p["dense"]["w"], p["dense"]["b"])


def _trunk(be: B.Backend, p: dict, images: jnp.ndarray) -> jnp.ndarray:
    """The network up to (and including) the dense layer, PRE-activation —
    the single definition of the paper's pipeline that `apply` (deployed,
    + output sigmoid) and `forward_logits` (training view) both run."""
    return _dense_preact(be, p, _conv_stages(be, p, images))


def conv_trunk(params: dict, images: jnp.ndarray, *,
               backend: str | B.Backend = "ref") -> jnp.ndarray:
    """The conv half of the pipeline as a separately callable stage:
    images (B,H,W,1) -> pooled feature maps (B,H/4,W/4[,1] by layout).

    This is the device-resident part of the paper's fabric (windowing ->
    MAC -> bias -> PLAN -> pool, twice); `dense_head` is the 49->10
    classifier that follows.  `apply(params, x) ==
    dense_head(params, conv_trunk(params, x))` on every backend — the
    FCN frame sweep (streaming/fcn_sweep.py) leans on this split to run
    the trunk ONCE per frame and re-use the feature map for every window.

    Single-frame calls on backends with a whole-frame megakernel (the
    fixed substrates' `frame_trunk` hook, kernels/frame_trunk) take the
    one-launch fast path; its interior map is word-identical to the
    composed stages, so the hook changes launches, not values.
    """
    be = B.get_backend(backend)
    p = be.prepare_params(params)
    x = jnp.asarray(images)
    if x.ndim == 4 and x.shape[0] == 1:
        quad = be.frame_trunk(x, p)
        if quad is not None:
            return quad[0]                     # interior == the plain trunk
    return _conv_stages(be, p, images)


def dense_head(params: dict, feats: jnp.ndarray, *,
               backend: str | B.Backend = "ref") -> jnp.ndarray:
    """The 49->10 dense classifier + output sigmoid over pooled feature
    maps ((B,7,7[,1]) backend layout, or already-flat (B,49))."""
    be = B.get_backend(backend)
    p = be.prepare_params(params)
    return _constrain_batch(be.sigmoid(_dense_preact(be, p, feats)))


def apply(params: dict, images: jnp.ndarray, *,
          backend: str | B.Backend = "ref") -> jnp.ndarray:
    """Single entry point: images (B,28,28,1) -> class scores (B,10).

    `params` may be float (quantizing backends convert them on the way in,
    idempotently) or already backend-native (e.g. the int32 pytree from
    `quantize_params_fixed`).  Scores are float in (0,1) for float-valued
    backends and Qm.n int32 words for "fixed" — `predict` handles both.

    Under `distributed.sharding.sharding_rules` (e.g. the vision-serving
    preset `make_vision_rules(mesh)`), every activation is constrained to
    shard its batch dim across the mesh — per-example compute is
    independent, so GSPMD splits the whole pipeline with zero collectives.
    """
    be = B.get_backend(backend)
    p = be.prepare_params(params)
    return _constrain_batch(be.sigmoid(_trunk(be, p, images)))


# ---------------------------------------------------------------------------
# Thin wrappers (the historical per-path entry points)
# ---------------------------------------------------------------------------

def forward(params: dict, images: jnp.ndarray, *, sigmoid=jax.nn.sigmoid) -> jnp.ndarray:
    """images (B,28,28,1) -> class scores (B,10). Float32 reference path."""
    if sigmoid is jax.nn.sigmoid:
        return apply(params, images, backend="ref")
    if sigmoid is fxp.sigmoid_plan_f32:
        return apply(params, images, backend="plan")
    return apply(params, images, backend=B.Backend(name="custom", sigmoid_fn=sigmoid))


def forward_plan(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    return apply(params, images, backend="plan")


def forward_fixed(qparams: dict, images: jnp.ndarray,
                  cfg: fxp.FixedPointConfig = fxp.Q16_16) -> jnp.ndarray:
    """Bit-faithful fixed-point inference. images float in [0,1] are
    quantized at the input port (the paper streams 8-bit pixels via DMA);
    returns fixed-point class scores (B,10) int32."""
    be = B.get_backend("fixed") if cfg == fxp.Q16_16 else B.FixedBackend(cfg=cfg)
    return apply(qparams, images, backend=be)


def forward_int8(qparams: dict, images: jnp.ndarray) -> jnp.ndarray:
    """int8 weights (dequant-on-use for conv; int8 MAC dense through the
    quant_matmul Pallas kernel)."""
    return apply(qparams, images, backend="int8")


def quantize_params_fixed(params: dict, cfg: fxp.FixedPointConfig = fxp.Q16_16) -> dict:
    """The paper's §III-B weight extraction: float Keras weights ->
    two's-complement fixed point, 'hardcoded' (returned as int32 pytree)."""
    return B.FixedBackend(cfg=cfg).quantize_params(params)


def quantize_params_int8(params: dict, cfg: ptq.QuantConfig = ptq.QuantConfig()) -> dict:
    return ptq.quantize_tree(params, cfg)


# ---------------------------------------------------------------------------
# Prediction / training objective
# ---------------------------------------------------------------------------

def predict(scores: jnp.ndarray) -> jnp.ndarray:
    """The paper's 'Max Finder' module (argmax is monotone, so it works on
    float scores and fixed-point int32 words alike)."""
    return jnp.argmax(scores, axis=-1)


def forward_logits(params: dict, images: jnp.ndarray) -> jnp.ndarray:
    """Pre-sigmoid class scores (B,10) on the float reference path.

    sigmoid is monotone, so argmax over these logits equals the deployed
    network's Max Finder over sigmoid scores — this is the training-side
    view of the SAME network (`_trunk` is shared with `apply`), not a
    different one."""
    be = B.get_backend("ref")
    return _trunk(be, be.prepare_params(params), images)


def loss_fn(params: dict, images: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Categorical crossentropy (paper §III-A) over the class scores.

    Training-only adaptation (documented in DESIGN.md): CCE through the
    output sigmoid has vanishing, seed-fragile gradients at this tiny width
    (two cascaded single-filter sigmoid convs start with near-constant
    features, and the earlier temperature-sharpened-scores variant stayed at
    chance for whole epochs on some seeds).  We apply CCE to the PRE-sigmoid
    logits instead: log_softmax is shift-invariant and sigmoid is monotone,
    so the *deployed* network (sigmoid + Max Finder argmax) is bit-identical
    to the paper's — only the training signal changes.
    """
    logp = jax.nn.log_softmax(forward_logits(params, images))
    onehot = jax.nn.one_hot(labels, 10)
    return -jnp.mean(jnp.sum(onehot * logp, axis=-1))


def accuracy(apply_fn, params, images, labels, batch: int = 256) -> float:
    hits, n = 0, 0
    for s in range(0, images.shape[0], batch):
        scores = apply_fn(params, images[s:s + batch])
        hits += int(jnp.sum(predict(scores) == labels[s:s + batch]))
        n += int(labels[s:s + batch].shape[0])
    return hits / max(n, 1)
