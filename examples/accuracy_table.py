"""Paper Table (§IV-C): classification accuracy across numeric paths.

Reproduces the paper's accuracy ladder (float CPU 93.47 % -> fixed-sim
88.03 % -> hardware 81 %) on the MNIST-proxy dataset, and extends it with
the paper's §III-B 'limitations of numerical representations' analysis: a
Qm.n fraction-bits sweep showing where fixed-point inference falls off.

    PYTHONPATH=src python examples/accuracy_table.py

Prints one `name,us_per_call,derived` CSV row per accuracy.
"""
from __future__ import annotations

import time

import jax.numpy as jnp

from repro.core import deploy, fixed_point as fxp, runtime, smallnet
from repro.data import synth_mnist


N_TEST = 1500


def run():
    t0 = time.perf_counter()
    trained = deploy.train_smallnet(n_train=8000, n_test=2000, epochs=16)
    n_test = N_TEST
    rows = []
    accs = deploy.evaluate_all_paths(trained.params, n_test=n_test)
    for name, acc in accs.items():
        rows.append((f"accuracy/{name}", None, f"acc={acc:.4f}"))
    # Q-format sweep: fixed-point accuracy vs fraction bits
    xte, yte = synth_mnist.make_dataset(n_test, seed=1)
    xte = jnp.asarray(xte); yte = jnp.asarray(yte)
    for frac in (4, 6, 8, 10, 12, 16, 20):
        cfg = fxp.FixedPointConfig(32, frac)
        qp = smallnet.quantize_params_fixed(trained.params, cfg)
        acc = smallnet.accuracy(
            lambda q, x: smallnet.forward_fixed(q, x, cfg), qp, xte, yte)
        rows.append((f"accuracy/fixed_q{31-frac}_{frac}", None, f"acc={acc:.4f}"))
    dt = (time.perf_counter() - t0) * 1e6
    rows.append(("accuracy_table_total", dt, f"n_test={n_test}"))
    return rows


def main() -> None:
    runtime.init_compile_cache()
    rows = run()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{'' if us is None else f'{us:.2f}'},{derived}")


if __name__ == "__main__":
    main()
