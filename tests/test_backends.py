"""Backend dispatch: registry coverage + cross-backend parity on the one
network graph (paper: one datapath, many substrates).

Every parity test runs twice: on `init_params` (zero biases) and on
`seeded_params` (every leaf perturbed), so bias handling cannot drift
unseen."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import smallnet

REQUIRED = {"ref", "plan", "pallas", "fixed", "fixed_pallas", "int8"}


@pytest.fixture(scope="module", params=("init", "perturbed"))
def case(request):
    return request.param


@pytest.fixture(scope="module")
def setup(case, rng):
    params = (smallnet.init_params(jax.random.key(1)) if case == "init"
              else smallnet.seeded_params())
    x = jnp.asarray(rng.uniform(0.0, 1.0, (6, 28, 28, 1)), jnp.float32)
    return params, x


def test_list_backends_covers_all_required():
    assert REQUIRED <= set(B.list_backends())


def test_get_backend_roundtrip_and_unknown():
    be = B.get_backend("pallas")
    assert be.name == "pallas"
    assert B.get_backend(be) is be                 # instance passthrough
    with pytest.raises(KeyError, match="registered"):
        B.get_backend("verilog")


def test_register_backend_decorator():
    @B.register_backend("_test_tmp")
    @dataclasses.dataclass(frozen=True)
    class Tmp(B.Backend):
        name: str = "_test_tmp"
    try:
        assert isinstance(B.get_backend("_test_tmp"), Tmp)
    finally:
        B._REGISTRY.pop("_test_tmp", None)


def test_apply_works_for_all_registered_backends_from_float_params(setup):
    params, x = setup
    for name in B.list_backends():
        scores = smallnet.apply(params, x, backend=name)
        assert scores.shape == (6, 10), name
        assert smallnet.predict(scores).shape == (6,), name


def test_ref_backend_is_forward(setup):
    params, x = setup
    np.testing.assert_array_equal(
        np.asarray(smallnet.apply(params, x, backend="ref")),
        np.asarray(smallnet.forward(params, x)))


def test_pallas_matches_ref_allclose(setup):
    params, x = setup
    got = smallnet.apply(params, x, backend="pallas")     # interpret mode
    want = smallnet.apply(params, x, backend="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pallas_plan_matches_plan_allclose(setup):
    params, x = setup
    got = smallnet.apply(params, x, backend="pallas_plan")
    want = smallnet.apply(params, x, backend="plan")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_fixed_matches_plan_within_qmn_tolerance(setup):
    """The fixed path IS the plan path in Qm.n words: dequantized scores must
    sit within a few quantization steps of the float PLAN scores."""
    params, x = setup
    fix = smallnet.apply(params, x, backend="fixed")
    assert fix.dtype == jnp.int32
    deq = fxp.from_fixed(fix, fxp.Q16_16)
    plan = smallnet.apply(params, x, backend="plan")
    # Q16.16 resolution is 2^-16; the 49-tap dense MAC accumulates ~50 steps
    np.testing.assert_allclose(np.asarray(deq), np.asarray(plan), atol=2e-3)


def test_fixed_wrapper_equals_backend_and_is_idempotent(setup):
    params, x = setup
    qfix = smallnet.quantize_params_fixed(params)
    via_wrapper = smallnet.forward_fixed(qfix, x)            # native params
    via_apply = smallnet.apply(params, x, backend="fixed")   # float params
    np.testing.assert_array_equal(np.asarray(via_wrapper), np.asarray(via_apply))
    # prepare_params must not double-quantize native int32 params
    be = B.get_backend("fixed")
    leaves = jax.tree_util.tree_leaves(be.prepare_params(qfix))
    np.testing.assert_array_equal(np.asarray(leaves[0]),
                                  np.asarray(jax.tree_util.tree_leaves(qfix)[0]))


def test_fixed_pallas_bit_exact_with_fixed(setup):
    """The contract of the fused kernel path: int32 WORD EQUALITY with the
    emulated fixed substrate — not closeness, identity."""
    params, x = setup
    got = smallnet.apply(params, x, backend="fixed_pallas")
    want = smallnet.apply(params, x, backend="fixed")
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fixed_pallas_bit_exact_in_saturate_and_trunc_modes(setup):
    params, x = setup
    for kw in ({"saturate": True}, {"round_nearest": False}):
        cfg = dataclasses.replace(fxp.Q16_16, **kw)
        got = smallnet.apply(params, x, backend=B.FixedPallasBackend(cfg=cfg))
        want = smallnet.apply(params, x, backend=B.FixedBackend(cfg=cfg))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=str(kw))


def test_fixed_pallas_matches_plan_within_qmn_tolerance(setup):
    """Same tolerance-based closeness to the float PLAN path as "fixed"."""
    params, x = setup
    deq = fxp.from_fixed(smallnet.apply(params, x, backend="fixed_pallas"),
                         fxp.Q16_16)
    plan = smallnet.apply(params, x, backend="plan")
    np.testing.assert_allclose(np.asarray(deq), np.asarray(plan), atol=2e-3)


def test_fixed_pallas_native_params_passthrough(setup):
    params, x = setup
    qfix = smallnet.quantize_params_fixed(params)
    np.testing.assert_array_equal(
        np.asarray(smallnet.apply(qfix, x, backend="fixed_pallas")),
        np.asarray(smallnet.apply(params, x, backend="fixed_pallas")))


def test_fused_conv_act_pool_hook_matches_composition(setup):
    """The new graph hook must equal maxpool(fused_conv_act(.)) for every
    backend — for fixed_pallas that means the single fused launch equals
    the three-launch composition, word for word."""
    params, x = setup
    for name in B.list_backends():
        be = B.get_backend(name)
        p = be.prepare_params(params)
        xi = be.ingest(x)
        fused = be.fused_conv_act_pool(xi, p["conv1"]["w"], p["conv1"]["b"])
        composed = be.maxpool2x2(
            be.fused_conv_act(xi, p["conv1"]["w"], p["conv1"]["b"]))
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(composed),
                                      err_msg=name)


def test_int8_matches_ref_within_ptq_tolerance(setup, case):
    params, x = setup
    got = smallnet.apply(params, x, backend="int8")
    want = smallnet.apply(params, x, backend="ref")
    err = float(jnp.abs(got - want).max())
    # int8 PTQ + PLAN sigmoid: scores move a little, ranking mostly survives
    assert err < 0.08
    if case == "perturbed":
        # the untrained perturbed weights leave the ten scores near-tied,
        # so the ranking is noise there; only the score error is bounded
        return
    agree = float(jnp.mean(smallnet.predict(got) == smallnet.predict(want)))
    assert agree >= 0.5


def test_int8_dense_uses_quant_matmul_kernel(setup):
    """The int8 dense layer must route through the Pallas quant_matmul
    wrapper, not the jnp oracle: same math, so compare against it."""
    params, x = setup
    from repro.core import ptq
    be = B.get_backend("int8")
    qp = be.quantize_params(params)
    feats = jnp.asarray(np.random.default_rng(2).uniform(0, 1, (6, 49)),
                        jnp.float32)
    got = be.dense(feats, qp["dense"]["w"], qp["dense"]["b"])
    xq = ptq.quantize(feats, ptq.QuantConfig(per_channel=False))
    wq = qp["dense"]["w"]
    want = ptq.quantized_matmul_ref(
        xq, ptq.QuantTensor(wq.q, wq.scale.reshape(-1))) + qp["dense"]["b"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_plan_wrapper(setup):
    params, x = setup
    np.testing.assert_array_equal(
        np.asarray(smallnet.forward_plan(params, x)),
        np.asarray(smallnet.apply(params, x, backend="plan")))


def test_apply_jits_per_backend(setup):
    params, x = setup
    fn = jax.jit(lambda p, xx: smallnet.apply(p, xx, backend="pallas_plan"))
    np.testing.assert_allclose(
        np.asarray(fn(params, x)),
        np.asarray(smallnet.apply(params, x, backend="pallas_plan")),
        rtol=1e-6, atol=1e-6)
