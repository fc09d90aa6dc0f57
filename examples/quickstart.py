"""Quickstart: the paper's entire pipeline in one script.

Trains smallNet in float (the Keras counterpart), extracts + converts the
weights to two's-complement fixed point, "bakes" them into the compiled
program, compares the accuracy ladder float -> PLAN -> fixed -> int8, then
demos the backend registry (one network graph, swappable substrates) and the
streaming vision serving engine.

    PYTHONPATH=src python examples/quickstart.py [--epochs 16] [--backend pallas]
"""
import argparse

import jax.numpy as jnp

from repro.core import backends, deploy, runtime, smallnet
from repro.data import synth_mnist
from repro.launch.mesh import make_serving_mesh
from repro.serving.router import ReplicaRouter
from repro.serving.vision_engine import VisionEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--n-train", type=int, default=6000)
    ap.add_argument("--backend", default="pallas",
                    choices=backends.list_backends(),
                    help="inference substrate for the serving demo")
    args = ap.parse_args()
    runtime.init_compile_cache()

    print("== 1. train float smallNet (paper §III-A: Adam, batch 64) ==")
    res = deploy.train_smallnet(n_train=args.n_train, n_test=1500,
                                epochs=args.epochs)
    print(f"   params={smallnet.param_count(res.params)} "
          f"train_acc={res.train_acc:.4f} test_acc={res.test_acc:.4f}")

    print("== 2. extract -> 2's-complement fixed point -> bake (§III-B) ==")
    qfix = smallnet.quantize_params_fixed(res.params)
    baked = deploy.bake(lambda q, x: smallnet.forward_fixed(q, x), qfix)
    x, y = synth_mnist.make_dataset(512, seed=2)
    pred = smallnet.predict(baked(jnp.asarray(x)))
    print(f"   baked fixed-point accuracy: {float((pred == y).mean()):.4f}")

    print("== 3. accuracy ladder (paper §IV-C: 93.47 -> 88.03 -> 81) ==")
    for name, acc in deploy.evaluate_all_paths(res.params, n_test=1500).items():
        print(f"   {name:24s} {acc:.4f}")

    print("== 4. backend registry: one graph, every substrate ==")
    xb, yb = synth_mnist.make_dataset(256, seed=4)
    xb = jnp.asarray(xb)
    ref_pred = smallnet.predict(smallnet.apply(res.params, xb, backend="ref"))
    for name in backends.list_backends():
        scores = smallnet.apply(res.params, xb, backend=name)  # float params in
        agree = float((smallnet.predict(scores) == ref_pred).mean())
        acc = float((smallnet.predict(scores) == jnp.asarray(yb)).mean())
        print(f"   backend={name:12s} acc={acc:.4f} argmax-agreement-vs-ref={agree:.4f}")

    # the fused fixed-point Pallas pipeline is not merely close to the
    # emulated fixed path — its int32 score words are identical
    fix = smallnet.apply(res.params, xb, backend="fixed")
    fixp = smallnet.apply(res.params, xb, backend="fixed_pallas")
    n_drift = int((fix != fixp).sum())
    print(f"   fixed vs fixed_pallas: {n_drift} of {fix.size} int32 words "
          f"differ ({'bit-exact' if n_drift == 0 else 'DRIFT'})")

    print(f"== 5. streaming vision engine on backend={args.backend!r} ==")
    # one jitted step sharded over the serving mesh: the batch axis splits
    # across every local device (degenerate on 1 CPU device, batch-DP on a
    # pod slice — same code either way)
    mesh = make_serving_mesh()
    eng = VisionEngine(res.params, backend=args.backend, batch_size=32,
                       mesh=mesh)
    eng.serve(list(synth_mnist.make_dataset(128, seed=6)[0]))
    s = eng.stats()
    print(f"   served n={s['n']} in {s['batches']} batched steps "
          f"(batch={s['batch_size']}, padded_slots={s['padded_slots']}, "
          f"mesh_devices={s['mesh_devices']})")
    print(f"   latency mean={s['latency_mean_ms']:.2f}ms "
          f"p50={s['latency_p50_ms']:.2f}ms p95={s['latency_p95_ms']:.2f}ms "
          f"throughput={s['throughput_qps']:.0f} img/s")

    print("== 5b. replica router: engine -> replicas -> mesh ==")
    # fleet-level serving: a least-loaded router over two replicas (here two
    # backends of the same weights — the paper's CPU + fabric, side by side),
    # drained concurrently with failover and aggregated fleet stats
    router = ReplicaRouter.from_backends(res.params,
                                         [args.backend, "fixed_pallas"],
                                         batch_size=32, mesh=mesh)
    router.serve(list(synth_mnist.make_dataset(128, seed=7)[0]))
    fs = router.stats()
    print(f"   fleet served n={fs['n']} over {fs['replicas']} replicas "
          f"(healthy={fs['healthy']}, served_by={fs['served_by']})")
    print(f"   fleet latency p50={fs['latency_p50_ms']:.2f}ms "
          f"p95={fs['latency_p95_ms']:.2f}ms "
          f"throughput={fs['throughput_qps']:.0f} img/s")

    print("== 6. latency (paper §IV-B: 560 ms CPU -> 109 ms FPGA, 5.1x) ==")
    sw = deploy.measure_latency(smallnet.forward, res.params)
    print(f"   deployed-baked latency: {sw*1e3:.3f} ms/image on this host")


if __name__ == "__main__":
    main()
