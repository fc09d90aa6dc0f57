"""The paper's end-to-end flow: train float -> extract -> quantize -> bake -> serve.

`bake()` mirrors 'weights ... hardcoded into the hardware': parameters are
closed over as Python constants so XLA constant-folds them into the compiled
program (the TPU analogue of baking into fabric — no weight arguments at all
in the executable's signature).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import smallnet
from repro.data import synth_mnist
from repro.optim import AdamConfig, adam_init, adam_update


@dataclasses.dataclass
class TrainResult:
    params: dict
    history: list
    train_acc: float
    test_acc: float


def train_smallnet(n_train: int = 8000, n_test: int = 2000, epochs: int = 8,
                   batch_size: int = 64, lr: float = 2e-2, seed: int = 0) -> TrainResult:
    """Paper §III-A: Adam, batch 64, 8 epochs.

    lr 2e-2 (not Keras' 1e-3 default): the 510-parameter net's features move
    glacially at small steps (see smallnet.loss_fn); 2e-2 trains to >= 0.80
    on the MNIST proxy across seeds where 5e-3 sat at chance for epochs."""
    xtr, ytr = synth_mnist.make_dataset(n_train, seed=seed)
    xte, yte = synth_mnist.make_dataset(n_test, seed=seed + 1)
    params = smallnet.init_params(jax.random.key(seed))
    cfg = AdamConfig(lr=lr, clip_norm=None)
    state = adam_init(params, cfg)

    @jax.jit
    def step(params, state, xb, yb):
        loss, grads = jax.value_and_grad(smallnet.loss_fn)(params, xb, yb)
        params, state, _ = adam_update(grads, state, params, cfg)
        return params, state, loss

    history = []
    for xb, yb in synth_mnist.batches(xtr, ytr, batch_size, seed=seed, epochs=epochs):
        params, state, loss = step(params, state, jnp.asarray(xb), jnp.asarray(yb))
        history.append(float(loss))
    fwd = jax.jit(smallnet.forward)
    train_acc = smallnet.accuracy(fwd, params, jnp.asarray(xtr), jnp.asarray(ytr))
    test_acc = smallnet.accuracy(fwd, params, jnp.asarray(xte), jnp.asarray(yte))
    return TrainResult(params, history, train_acc, test_acc)


def bake(apply_fn: Callable, params: Any) -> Callable:
    """Bake weights as compile-time constants (paper: hardcoded into fabric)."""
    return jax.jit(lambda x: apply_fn(params, x))


def evaluate_all_paths(params: dict, n_test: int = 2000, seed: int = 1) -> dict:
    """The paper's accuracy table: float (CPU) vs PLAN-sigmoid vs fixed-point
    'post-synthesis simulation' vs int8, on the same test set."""
    xte, yte = synth_mnist.make_dataset(n_test, seed=seed)
    xte = jnp.asarray(xte); yte = jnp.asarray(yte)
    qfix = smallnet.quantize_params_fixed(params)
    qint8 = smallnet.quantize_params_int8(params)
    paths = {
        "float32": jax.jit(smallnet.forward),
        "float32_plan_sigmoid": jax.jit(smallnet.forward_plan),
        "fixed_q16_16": jax.jit(lambda q, x: smallnet.forward_fixed(q, x)),
        "int8_ptq": jax.jit(smallnet.forward_int8),
    }
    out = {}
    for name, fn in paths.items():
        p = {"float32": params, "float32_plan_sigmoid": params,
             "fixed_q16_16": qfix, "int8_ptq": qint8}[name]
        out[name] = smallnet.accuracy(fn, p, xte, yte)
    return out


def measure_latency(apply_fn: Callable, params: Any, batch: int = 1,
                    iters: int = 50) -> float:
    """Wall-clock per-inference latency (seconds) on this host (the paper's
    'software inference time' analogue)."""
    x = jnp.zeros((batch, 28, 28, 1), jnp.float32)
    fn = jax.jit(lambda p, x: apply_fn(p, x))
    fn(params, x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn(params, x).block_until_ready()
    return (time.perf_counter() - t0) / iters
