"""Backend dispatch for smallNet — one network graph, swappable substrates.

The paper's whole point is one datapath (windowing -> parallel MAC -> bias ->
PLAN sigmoid -> maxpool) realized on different substrates: a Keras float
model on the PS-side CPU and a fixed-point Verilog pipeline in the fabric.
This module makes that explicit: the network graph lives once in
`smallnet.apply`, and a *backend* supplies the five layer primitives

    conv2x2_same(x, w, b)   pre-activation 2x2 SAME conv
    maxpool2x2(x)           2x2/2 max pool
    dense(x, w, b)          pre-activation fully-connected layer
    sigmoid(x)              the activation unit
    quantize_params(params) float pytree -> backend-native parameters

plus optional layout hooks (`ingest`, `flatten`, `fused_conv_act`) for
substrates whose tensor format differs from NHWC float (the fixed-point
path carries (B, H, W) int32 words, exactly the Verilog BRAM layout).

Multi-channel graphs (`core/resnet8.py`) use four more primitives, on NHWC
activations in every backend (float, or int32 words on the fixed ones):

    ingest_channels(images) (B,H,W,C) float images -> activations
    conv(x, w, b, stride)   pre-activation k x k SAME conv, HWIO weights
    relu(x)                 max(x, 0)
    global_avgpool(x)       (B,H,W,C) -> (B,C) mean over H x W

and `accumulate(a, b)` as the residual add.

Registered backends (mirroring TinyCNN/ZynqNet-style swappable layer
engines over one fixed graph):

    ref          float32 XLA ops, exact sigmoid — the Keras counterpart
    plan         float32 XLA ops, PLAN piecewise-linear sigmoid
    pallas       Pallas TPU kernels (conv2d with fused-sigmoid epilogue,
                 maxpool2d comparator tree), exact sigmoid — matches `ref`
    pallas_plan  Pallas kernels with the fused conv+PLAN epilogue and the
                 sigmoid_pla VPU kernel — matches `plan`
    fixed        bit-faithful Qm.n two's-complement datapath (§III-B),
                 emulated with jnp int ops
    fixed_pallas the same Qm.n words through the FUSED kernels/fixed_conv
                 Pallas pipeline (windowing+limb-MAC+bias+PLAN+maxpool in
                 one launch) + the fixed_dense MAC launch — int32 bit-exact
                 with "fixed"
    int8         TPU-native PTQ: int8 dense MAC through the quant_matmul
                 MXU kernel, dequant-on-use convs, PLAN sigmoid

Usage:

    from repro.core import smallnet
    scores = smallnet.apply(params, images, backend="pallas")

`apply` accepts float params for every backend (they are quantized on the
way in, idempotently), or pre-quantized params produced by the backend's
own `quantize_params`.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import fixed_point as fxp
from repro.core import ptq
from repro.core import runtime
from repro.kernels.conv2d.ops import conv2d
from repro.kernels.fixed_conv.ops import (fixed_conv2d, fixed_maxpool2x2,
                                          fixed_sigmoid)
from repro.kernels.fixed_conv_mc.ops import fixed_conv_mc, im2col, same_padding
from repro.kernels.frame_trunk.ops import frame_trunk_quad
from repro.kernels.maxpool2d.ops import maxpool2d
from repro.kernels.quant_matmul.ops import fixed_dense, quant_matmul
from repro.kernels.sigmoid_pla.ops import sigmoid_pla

# the process-wide interpret switch, re-exported here because
# the backend registry is where callers already look for substrate knobs
set_interpret = runtime.set_interpret
interpret_default = runtime.interpret_default


# ---------------------------------------------------------------------------
# Shared float primitives (the XLA reference datapath)
# ---------------------------------------------------------------------------

# The float path is the f32 reference the other substrates are held to, so
# its MACs are pinned to full f32: at default precision a TPU rounds f32
# conv/matmul operands to bf16, and the reference would drift from itself
# across platforms by more than the parity tolerances.
_F32 = jax.lax.Precision.HIGHEST


def conv_same_2x2(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """2x2 SAME conv, NHWC/HWIO. Keras pads SAME for even kernels as
    (0 before, 1 after) on each spatial dim."""
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=((0, 1), (0, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_F32)
    return y + b


def dense_f32(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Float fully-connected layer, pre-activation: x @ w + b."""
    return jnp.dot(x, w, precision=_F32) + b


def maxpool_2x2(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def conv_same(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              stride: int) -> jnp.ndarray:
    """k x k conv with TensorFlow SAME padding, NHWC/HWIO, plus bias."""
    kh, kw = w.shape[:2]
    pads = [same_padding(n, k, stride)[1:]
            for n, k in zip(x.shape[1:3], (kh, kw))]
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_F32)
    return y + b


# ---------------------------------------------------------------------------
# Fixed-point primitives (the Verilog datapath, emulated bit-exactly)
# ---------------------------------------------------------------------------

def windows_2x2_same(x: jnp.ndarray) -> jnp.ndarray:
    """The windowing module: (B,H,W) -> (B,H,W,4) of 2x2 patches with SAME
    (0 before, 1 after) zero padding. Mirrors the Verilog line-buffer."""
    xp = jnp.pad(x, ((0, 0), (0, 1), (0, 1)))
    return jnp.stack([xp[:, :-1, :-1], xp[:, :-1, 1:],
                      xp[:, 1:, :-1], xp[:, 1:, 1:]], axis=-1)


def conv_fixed(x: jnp.ndarray, w4: jnp.ndarray, b: jnp.ndarray,
               cfg: fxp.FixedPointConfig) -> jnp.ndarray:
    """Fixed-point conv: 4 parallel MACs per output pixel + bias add.
    x (B,H,W) int32 fixed; w4 (4,) int32 fixed; b () int32 fixed."""
    win = windows_2x2_same(x)                             # (B,H,W,4)
    prods = fxp.fixed_mul(win, w4.reshape(1, 1, 1, 4), cfg)
    acc = jnp.sum(prods, axis=-1, dtype=jnp.int32)        # MAC accumulate
    return fxp.fixed_add(acc, b, cfg)


def conv_fixed_mc(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                  stride: int, cfg: fxp.FixedPointConfig) -> jnp.ndarray:
    """Multi-channel fixed-point conv: every output word is the MAC array
    over its k x k x C_in patch (`fixed_matmul`) plus the bias word.
    x (B,H,W,Cin) int32, w (kh,kw,Cin,Cout) int32, b (Cout,) int32."""
    kh, kw, cin, cout = w.shape
    cols, (B, Ho, Wo) = im2col(x, kh, kw, stride)           # (K, M)
    acc = fxp.fixed_matmul(cols.T, w.reshape(-1, cout), cfg)
    y = fxp.fixed_add(acc, b.reshape(1, -1), cfg)
    return y.reshape(B, Ho, Wo, cout)


def global_avgpool_fixed(x: jnp.ndarray,
                         cfg: fxp.FixedPointConfig) -> jnp.ndarray:
    """(B,H,W,C) words -> (B,C): the int32 (wraparound) sum of the H*W
    words of a channel, then a right shift by log2(H*W) that rounds as a
    product does — the pooling unit of a datapath without a divider, so
    H*W must be a power of two."""
    n = x.shape[1] * x.shape[2]
    if n & (n - 1):
        raise ValueError(f"the shift pool needs a power-of-two extent, "
                         f"got {x.shape[1]}x{x.shape[2]}")
    s = jnp.sum(x, axis=(1, 2), dtype=jnp.int32)
    y = fxp.shift_right_round(s, n.bit_length() - 1, cfg.round_nearest)
    return fxp._wrap_to_bits(y, cfg.total_bits)


def maxpool_fixed(x: jnp.ndarray) -> jnp.ndarray:
    """(B,H,W) int32 -> (B,H/2,W/2): comparator tree, exact in any format."""
    return jnp.maximum(jnp.maximum(x[:, ::2, ::2], x[:, ::2, 1::2]),
                       jnp.maximum(x[:, 1::2, ::2], x[:, 1::2, 1::2]))


# ---------------------------------------------------------------------------
# Backend base class + registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Backend:
    """Float32 XLA reference backend ("ref"); base class for all others.

    Subclasses override the five primitives; the layout hooks have sane
    float/NHWC defaults.  Instances are immutable so they can be closed
    over by jit without hashing surprises.
    """
    name: str = "ref"
    sigmoid_fn: Callable[[jnp.ndarray], jnp.ndarray] = jax.nn.sigmoid

    # -- the five primitives ------------------------------------------------
    def quantize_params(self, params):
        """Float param pytree -> backend-native params (identity here)."""
        return params

    def conv2x2_same(self, x, w, b):
        return conv_same_2x2(x, w, b)

    def maxpool2x2(self, x):
        return maxpool_2x2(x)

    def dense(self, x, w, b):
        return dense_f32(x, w, b)

    def sigmoid(self, x):
        return self.sigmoid_fn(x)

    # -- multi-channel primitives (NHWC) -------------------------------------
    def ingest_channels(self, images):
        """(B,H,W,C) float images -> NHWC activations, channels kept."""
        return images

    def conv(self, x, w, b, stride: int = 1):
        """Pre-activation k x k conv, TensorFlow SAME, HWIO weights."""
        return conv_same(x, w, b, stride)

    def relu(self, x):
        return jnp.maximum(x, jnp.zeros((), x.dtype))

    def global_avgpool(self, x):
        return jnp.mean(x, axis=(1, 2))

    # -- layout hooks -------------------------------------------------------
    def params_native(self, params) -> bool:
        """True if `params` are already in this backend's native format."""
        return True

    def prepare_params(self, params):
        """Idempotent: quantize float params, pass native params through."""
        return params if self.params_native(params) else self.quantize_params(params)

    def ingest(self, images):
        """(B,28,28,1) float images -> backend activation tensor."""
        return images

    def flatten(self, x):
        return x.reshape(x.shape[0], -1)

    def fused_conv_act(self, x, w, b):
        """conv + activation; backends with a fused epilogue override this."""
        return self.sigmoid(self.conv2x2_same(x, w, b))

    def accumulate(self, a, b):
        """Add two PRE-ACTIVATION conv partial sums — or a residual branch
        to its shortcut (core/resnet8.py) — in this backend's word domain.
        The FCN frame sweep (streaming/fcn_sweep.py) decomposes a
        conv whose taps read from different feature maps into per-map
        masked-weight convs and sums them; for the default float domain
        that's plain `+`, while fixed-point backends override with
        `fixed_add` so the running sum re-enters the Qm.n word width after
        every step (wraparound addition is associative mod 2**bits, which is
        what makes the decomposition bit-exact)."""
        return a + b

    def mask_conv_weight(self, w, mask):
        """Zero out conv taps: w (2,2,1,1) backend-native, mask (2,2) of
        0/1.  Tap-masking is how the sweep reproduces a patch's SAME-padding
        zeros mid-frame (a zeroed tap contributes exactly 0 to the MAC in
        every word domain).  Backends whose weights aren't plain arrays
        (int8 QuantTensor) override."""
        return w * jnp.asarray(mask, w.dtype).reshape(2, 2, 1, 1)

    def fused_conv_act_pool(self, x, w, b):
        """conv + activation + 2x2 maxpool — the full paper pipeline stage.
        Default composes the two hooks; backends whose kernel fuses the pool
        into the same launch (fixed_pallas) override this."""
        return self.maxpool2x2(self.fused_conv_act(x, w, b))

    def frame_trunk(self, frames, p):
        """Whole-frame trunk fast path: (1, H, W, 1) float frames + native
        params -> the level-2 role-map quad (I, B, R, C), each (1, H/4,
        W/4) in the backend's layout — or None when this backend has no
        megakernel (or the geometry doesn't qualify), in which case callers
        fall back to the composed per-stage path.  The fixed substrates
        override this with the `kernels/frame_trunk` one-launch megakernel;
        `smallnet.conv_trunk` and `FcnSweep` route through it."""
        return None


_REGISTRY: dict[str, Backend] = {}


def register_backend(name: str, backend: Backend | None = None):
    """Register a backend instance under `name`.

    Usable directly — ``register_backend("ref", Backend())`` — or as a
    class decorator::

        @register_backend("mine")
        @dataclasses.dataclass(frozen=True)
        class MyBackend(Backend): ...
    """
    if backend is not None:
        _REGISTRY[name] = backend
        return backend

    def deco(cls):
        _REGISTRY[name] = cls() if isinstance(cls, type) else cls
        return cls
    return deco


def get_backend(backend: str | Backend) -> Backend:
    if isinstance(backend, Backend):
        return backend
    try:
        return _REGISTRY[backend]
    except KeyError:
        raise KeyError(
            f"unknown backend {backend!r}; registered: {list_backends()}") from None


def list_backends() -> list[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# Float backends: ref / plan
# ---------------------------------------------------------------------------

register_backend("ref", Backend())
register_backend("plan", Backend(name="plan", sigmoid_fn=fxp.sigmoid_plan_f32))


# ---------------------------------------------------------------------------
# Pallas backends: the kernels/ wrappers wired into the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PallasBackend(Backend):
    """Runs convs + pools through the Pallas TPU kernels.

    `activation` selects the fused conv epilogue: "sigmoid" (exact, matches
    "ref") or "plan" (the PLAN piecewise-linear epilogue, matches "plan");
    the standalone activation after the dense layer uses the matching
    implementation (sigmoid_pla VPU kernel for "plan").
    `interpret=None` follows the process-wide `core.runtime` default
    (the interpreter on a CPU backend, compiled kernels on a TPU); an
    explicit bool pins this instance regardless of the default.
    """
    name: str = "pallas"
    activation: str = "sigmoid"
    interpret: bool | None = None

    def conv2x2_same(self, x, w, b):
        return conv2d(x, w, b, padding="SAME",
                                interpret=self.interpret)

    def fused_conv_act(self, x, w, b):
        # the fused epilogue: bias + activation inside the conv kernel
        return conv2d(x, w, b, padding="SAME",
                                activation=self.activation,
                                interpret=self.interpret)

    def maxpool2x2(self, x):
        return maxpool2d(x, interpret=self.interpret)

    def sigmoid(self, x):
        if self.activation == "plan":
            return sigmoid_pla(x, interpret=self.interpret)
        return jax.nn.sigmoid(x)


register_backend("pallas", PallasBackend())
register_backend("pallas_plan", PallasBackend(name="pallas_plan",
                                              activation="plan"))


# ---------------------------------------------------------------------------
# Fixed-point backend: the paper's Verilog datapath
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FixedBackend(Backend):
    """Bit-faithful Qm.n two's-complement path (paper §III-B, Fig. 4).

    Activations are (B, H, W) int32 words (no channel dim — the fabric
    streams one feature map); images are quantized at the input port, and
    the returned class scores are fixed-point int32.
    """
    name: str = "fixed"
    cfg: fxp.FixedPointConfig = fxp.Q16_16

    def quantize_params(self, params):
        """The paper's §III-B weight extraction: float Keras weights ->
        two's-complement fixed point (int32 pytree)."""
        return jax.tree_util.tree_map(lambda p: fxp.to_fixed(p, self.cfg), params)

    def params_native(self, params) -> bool:
        leaves = jax.tree_util.tree_leaves(params)
        return bool(leaves) and all(
            jnp.issubdtype(l.dtype, jnp.integer) for l in leaves)

    def ingest(self, images):
        # the paper streams 8-bit pixels via DMA; quantize at the port
        return fxp.to_fixed(images[..., 0], self.cfg)     # (B,28,28)

    def conv2x2_same(self, x, w, b):
        # w (2,2,1,1) int32 -> the 4 MAC taps; b (1,) -> scalar bias word
        return conv_fixed(x, w.reshape(4), b[0], self.cfg)

    def ingest_channels(self, images):
        return fxp.to_fixed(images, self.cfg)             # (B,H,W,C) words

    def conv(self, x, w, b, stride: int = 1):
        return conv_fixed_mc(x, w, b, stride, self.cfg)

    def global_avgpool(self, x):
        return global_avgpool_fixed(x, self.cfg)

    def maxpool2x2(self, x):
        return maxpool_fixed(x)

    def dense(self, x, w, b):
        y = fxp.fixed_matmul(x, w, self.cfg)
        return fxp.fixed_add(y, b.reshape(1, -1), self.cfg)

    def sigmoid(self, x):
        return fxp.fixed_sigmoid_plan(x, self.cfg)

    def accumulate(self, a, b):
        # wraparound fixed add is associative mod 2**total_bits, so partial
        # conv sums recombine to exactly the single-conv accumulator word
        # (saturate mode is NOT associative; the sweep rejects it up front)
        return fxp.fixed_add(a, b, self.cfg)

    def frame_trunk(self, frames, p):
        # ONE Pallas launch for the whole trunk + quad role maps (the
        # kernels/frame_trunk megakernel) — inherited by fixed_pallas, so
        # both fixed substrates share the identical launch.  Word-exact
        # with the composed path; geometry that can't tile (batch > 1,
        # non-multiple-of-4 extents, saturating cfg) falls back by
        # returning None.
        B_, H, W = frames.shape[0], frames.shape[1], frames.shape[2]
        if B_ != 1 or H % 4 or W % 4 or H < 4 or W < 4 or self.cfg.saturate:
            return None
        x = self.ingest(frames)                      # (1, H, W) int32 words
        quad = frame_trunk_quad(
            x[0], p["conv1"]["w"], p["conv1"]["b"],
            p["conv2"]["w"], p["conv2"]["b"], cfg=self.cfg,
            interpret=getattr(self, "interpret", None))
        # the barrier pins the (4, H/4, W/4) kernel output before it is
        # split into per-role maps: without it, inlining this call into a
        # larger traced program lets XLA fuse the slices into the
        # interpret-mode pallas emulation, which corrupts the corner map's
        # lane-remainder columns (last W/4 % 8 output cols) whenever the
        # kernel operands are intermediates rather than program parameters
        quad = jax.lax.optimization_barrier(quad)
        return tuple(quad[k][None] for k in range(4))


register_backend("fixed", FixedBackend())


@dataclasses.dataclass(frozen=True)
class FixedPallasBackend(FixedBackend):
    """The bit-faithful Qm.n datapath as FUSED Pallas launches.

    Same arithmetic contract as "fixed" (it reuses `FixedBackend
    .quantize_params` and the `fixed_point` word semantics), but each
    pipeline stage is one kernel launch from kernels/fixed_conv — and the
    conv+PLAN+maxpool stage is a SINGLE launch via `fused_conv_act_pool`,
    the TPU analogue of the paper's fully fused fabric pipeline.  Output
    words are int32-identical to the emulated "fixed" backend (asserted by
    the golden-vector and hypothesis batteries in tests/).  `interpret=None`
    follows the process-wide `core.runtime` switch.
    """
    name: str = "fixed_pallas"
    interpret: bool | None = None

    def _w4(self, w):
        # (2,2,1,1) int32 weight -> the 4 MAC taps, row-major like the
        # emulated path's `w.reshape(4)`
        return w.reshape(4)

    def conv2x2_same(self, x, w, b):
        return fixed_conv2d(x, self._w4(w), b, cfg=self.cfg,
                            interpret=self.interpret)

    def fused_conv_act(self, x, w, b):
        return fixed_conv2d(x, self._w4(w), b, cfg=self.cfg,
                            activation="plan", interpret=self.interpret)

    def fused_conv_act_pool(self, x, w, b):
        # windowing -> limb MAC -> bias -> PLAN -> maxpool, one launch
        return fixed_conv2d(x, self._w4(w), b, cfg=self.cfg,
                            activation="plan", pool=True,
                            interpret=self.interpret)

    def maxpool2x2(self, x):
        return fixed_maxpool2x2(x, interpret=self.interpret)

    def conv(self, x, w, b, stride: int = 1):
        # the multi-channel MAC array, one kernels/fixed_conv_mc launch
        return fixed_conv_mc(x, w, b, stride=stride, cfg=self.cfg,
                             interpret=self.interpret)

    def dense(self, x, w, b):
        return fixed_dense(x, w, b, cfg=self.cfg, interpret=self.interpret)

    def sigmoid(self, x):
        return fixed_sigmoid(x, cfg=self.cfg, interpret=self.interpret)


register_backend("fixed_pallas", FixedPallasBackend())


# ---------------------------------------------------------------------------
# int8 backend: TPU-native PTQ with the quant_matmul MXU kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Int8Backend(Backend):
    """int8 weights: dequant-on-use for the (tiny) convs, true int8 MAC for
    the dense layer through the kernels/quant_matmul Pallas wrapper —
    activations are quantized per-tensor on the fly, weights carry
    per-channel scales, accumulation is exact int32 with a fused dequant
    epilogue (the MXU analogue of the paper's DSP MAC array).
    `interpret=None` follows the process-wide `core.runtime` switch."""
    name: str = "int8"
    qcfg: ptq.QuantConfig = ptq.QuantConfig()
    interpret: bool | None = None

    def quantize_params(self, params):
        return ptq.quantize_tree(params, self.qcfg)

    def params_native(self, params) -> bool:
        return any(isinstance(l, ptq.QuantTensor)
                   for l in jax.tree_util.tree_leaves(
                       params, is_leaf=lambda x: isinstance(x, ptq.QuantTensor)))

    def conv2x2_same(self, x, w, b):
        w = w.dequantize() if isinstance(w, ptq.QuantTensor) else w
        return conv_same_2x2(x, w, b)

    def mask_conv_weight(self, w, mask):
        # conv weights are dequant-on-use anyway, so mask the float view
        # (conv2x2_same passes plain arrays straight through)
        w = w.dequantize() if isinstance(w, ptq.QuantTensor) else w
        return w * jnp.asarray(mask, w.dtype).reshape(2, 2, 1, 1)

    def dense(self, x, w, b):
        if not isinstance(w, ptq.QuantTensor):           # float fallback
            return dense_f32(x, w, b)
        xq = ptq.quantize(x, dataclasses.replace(self.qcfg, per_channel=False))
        y = quant_matmul(xq.q, w.q, xq.scale.reshape(()),
                            w.scale.reshape(-1), interpret=self.interpret)
        return y + b

    def sigmoid(self, x):
        return fxp.sigmoid_plan_f32(x)


register_backend("int8", Int8Backend())
