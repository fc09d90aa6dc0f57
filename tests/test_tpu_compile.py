"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed on CPU hosts and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot: a
lowering Mosaic refuses (strided gathers, scalar bitcasts), a block shape
that breaks the (8, 128) rule, a kernel over its VMEM limit.  Nothing runs;
each test asserts that the compiled program contains the Mosaic kernel
(`tpu_custom_call`), i.e. that the Pallas path — not the interpreter — was
compiled.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and pytest-xdist workers all
import this file.  The persistent compilation cache is off around the
compiles, since a described-chip executable cannot be read back on a host
without the chip.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import resnet8, runtime, smallnet
from repro.kernels.fixed_conv.ops import fixed_conv2d, fixed_maxpool2x2
from repro.kernels.fixed_conv_mc.ops import fixed_conv_mc
from repro.kernels.frame_trunk import frame_trunk_quad
from repro.kernels.maxpool2d.ops import maxpool2d
from repro.kernels.quant_matmul.ops import fixed_dense
from repro.streaming import fcn_sweep
from repro.streaming.fcn_sweep import FcnSweep


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Compiled (not interpreted) kernels, on one described chip, with the
    persistent compilation cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    interpret = runtime.interpret_default()
    cache = jax.config.jax_enable_compilation_cache
    runtime.set_interpret(False)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache)
    compilation_cache.reset_cache()
    runtime.set_interpret(interpret)


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


_I32, _F32 = jnp.int32, jnp.float32


@pytest.mark.parametrize("side", (112, 512))
def test_frame_trunk_compiles(one_chip, side):
    """112 is one tile; 512 splits into two tiles and crosses a seam."""
    _compile(one_chip,
             lambda x, w1, b1, w2, b2: frame_trunk_quad(x, w1, b1, w2, b2),
             ((side, side), _I32), ((4,), _I32), ((1,), _I32),
             ((4,), _I32), ((1,), _I32))


@pytest.mark.parametrize("pool", (False, True))
def test_fixed_conv2d_compiles(one_chip, pool):
    _compile(one_chip,
             lambda x, w, b: fixed_conv2d(x, w, b, activation="plan",
                                          pool=pool),
             ((8, 28, 28), _I32), ((4,), _I32), ((1,), _I32))


def test_fixed_maxpool2x2_compiles(one_chip):
    _compile(one_chip, fixed_maxpool2x2, ((8, 28, 28), _I32))


def test_maxpool2d_compiles(one_chip):
    _compile(one_chip, maxpool2d, ((8, 28, 28, 1), _F32))


def test_fixed_dense_compiles(one_chip):
    _compile(one_chip, lambda x, w, b: fixed_dense(x, w, b, cfg=fxp.Q16_16),
             ((8, 49), _I32), ((49, 10), _I32), ((10,), _I32))


def test_fixed_pallas_sweep_compiles(one_chip):
    """The whole per-frame program of the streaming main path: the
    frame_trunk megakernel plus the fixed_dense window head."""
    sweep = FcnSweep(stride=8)
    pos = tuple(sweep.positions((112, 112)))
    fn = fcn_sweep._sweep_fn(B.get_backend("fixed_pallas"), (112, 112),
                             sweep.patch, pos)
    params = jax.eval_shape(smallnet.init_params, jax.random.key(0))
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (params, jax.ShapeDtypeStruct((1, 112, 112, 1), _F32)))
    text = fn.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 2    # trunk + dense head


@pytest.mark.parametrize("side,cin,cout,k,stride", [
    (32, 3, 16, 3, 1), (32, 16, 16, 3, 1), (32, 16, 32, 3, 2),
    (16, 32, 64, 1, 2), (8, 64, 64, 3, 1)])
def test_fixed_conv_mc_compiles(one_chip, side, cin, cout, k, stride):
    """ResNet-8's conv layers at batch 32: the stem, a stack-1 conv, the
    stride-2 3x3 and 1x1 convs, and the widest reduction (K = 576)."""
    _compile(one_chip,
             lambda x, w, b: fixed_conv_mc(x, w, b, stride=stride),
             ((32, side, side, cin), _I32), ((k, k, cin, cout), _I32),
             ((cout,), _I32))


def test_resnet8_step_compiles(one_chip):
    """The engine's whole ResNet-8 step on fixed_pallas at batch 32: nine
    conv launches and the dense head."""
    be = B.get_backend("fixed_pallas")
    params = jax.eval_shape(resnet8.init_params, jax.random.key(0))
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (params, jax.ShapeDtypeStruct((32, 32, 32, 3), _F32)))
    fn = jax.jit(lambda p, x: resnet8.apply(p, x, backend=be))
    text = fn.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 10
