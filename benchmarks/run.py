# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table + the scale deliverables.

    PYTHONPATH=src python -m benchmarks.run [--fast]
    PYTHONPATH=src python -m benchmarks.run --backends   # parity smoke, no training

  accuracy_table  — paper §IV-C accuracy ladder + Qm.n degradation sweep
  latency_table   — paper §IV-B software vs deployed latency / speedup
  resource_table  — paper §IV-A resources/power analogues + per-arch HBM
  roofline_table  — three-term roofline per (arch x shape), single pod

`--backends` runs one tiny batch through every registered inference backend
(ref / plan / pallas / pallas_plan / fixed / fixed_pallas / int8) plus a
mini vision-engine drain, checks parity against the reference substrate
(and int32 WORD EQUALITY between fixed and fixed_pallas — the fused-kernel
bit-exactness contract), and exits nonzero on failure — catches benchmark
drift without a full training run.
"""
import argparse
import sys


def _emit(rows):
    for name, us, derived in rows:
        us_s = f"{us:.2f}" if us is not None else ""
        print(f"{name},{us_s},{derived}")


def backend_smoke() -> int:
    """Tiny-batch parity sweep over every registered backend. Returns a
    process exit code (0 = all substrates agree within tolerance)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backends, smallnet
    from repro.core import fixed_point as fxp
    from repro.data import synth_mnist
    from repro.serving.vision_engine import VisionEngine

    params = smallnet.init_params(jax.random.key(0))
    # init_params zeroes the biases, which would make bias-handling drift
    # invisible to the parity check — give every leaf a nonzero value
    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    params = jax.tree_util.tree_unflatten(treedef, [
        l + 0.1 * jax.random.normal(k, l.shape, l.dtype)
        for l, k in zip(leaves, keys)])
    x = jnp.asarray(synth_mnist.make_dataset(8, seed=0)[0])
    ref = smallnet.apply(params, x, backend="ref")
    plan = smallnet.apply(params, x, backend="plan")
    # (comparison target, max-abs-error tolerance) per substrate
    spec = {
        "ref": (ref, 0.0),
        "plan": (plan, 0.0),
        "pallas": (ref, 1e-4),          # interpret-mode float assoc. noise
        "pallas_plan": (plan, 1e-4),
        "fixed": (plan, 5e-3),          # Q16.16 quantization steps
        "fixed_pallas": (plan, 5e-3),   # same Qm.n words as "fixed"
        "int8": (ref, 0.15),            # int8 PTQ + PLAN sigmoid
    }
    print("name,us_per_call,derived")
    failed = False
    for name in backends.list_backends():
        scores = smallnet.apply(params, x, backend=name)
        if scores.dtype == jnp.int32:
            scores = fxp.from_fixed(scores)
        want, tol = spec.get(name, (ref, 0.05))   # conservative for extras
        err = float(jnp.abs(scores - want).max())
        ok = err <= tol
        failed |= not ok
        print(f"smoke/parity_{name},,max_err={err:.2e} tol={tol:g} "
              f"{'OK' if ok else 'FAIL'}")
    # the fused fixed kernel's contract is stronger than a tolerance: its
    # int32 words must be IDENTICAL to the emulated fixed substrate
    fix = smallnet.apply(params, x, backend="fixed")
    fixp = smallnet.apply(params, x, backend="fixed_pallas")
    n_drift = int(jnp.sum(fix != fixp))
    ok = n_drift == 0
    failed |= not ok
    print(f"smoke/bitexact_fixed_pallas,,drifted_words={n_drift}/"
          f"{fix.size} {'OK' if ok else 'FAIL'}")
    # mini engine drain: the serving path must work for every backend too
    for name in backends.list_backends():
        eng = VisionEngine(params, backend=name, batch_size=4, warmup=False)
        res = eng.serve(list(np.asarray(x)))
        s = eng.stats()
        # a faulted step sheds its batch instead of raising, so a shed
        # here is a failure even when the caller got an answer back
        ok = (len(res) == 8 and all(r is not None and r.latency_s > 0
                                    for r in res)
              and s["shed"] == 0 and s["accounted"])
        failed |= not ok
        print(f"smoke/engine_{name},{s['latency_mean_ms']*1e3:.2f},"
              f"served={s['n']} {'OK' if ok else 'FAIL'}")
    print(f"smoke/result,,{'FAIL' if failed else 'OK'}")
    return 1 if failed else 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller smallNet training run")
    ap.add_argument("--backends", action="store_true",
                    help="backend parity smoke (tiny batch, no training); "
                         "exits nonzero on parity failure")
    args = ap.parse_args()
    from repro.core import runtime
    runtime.init_compile_cache()

    if args.backends:
        sys.exit(backend_smoke())

    from benchmarks import accuracy_table, latency_table, resource_table, roofline_table
    from repro.core import deploy

    print("name,us_per_call,derived")
    trained = deploy.train_smallnet(
        n_train=3000 if args.fast else 8000,
        n_test=800 if args.fast else 2000,
        epochs=8 if args.fast else 16)
    rows, trained = accuracy_table.run(trained=trained,
                                       n_test=800 if args.fast else 1500)
    _emit(rows)
    _emit(latency_table.run(trained))
    _emit(resource_table.run(trained))
    _emit(roofline_table.run())


if __name__ == "__main__":
    main()
