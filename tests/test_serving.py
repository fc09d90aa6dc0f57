"""Serving engine: batched decode, continuous refill, quantized deployment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import ptq
from repro.models import model as M
from repro.serving.engine import Engine, Request


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("granite-3-2b").smoke()
    m = M.build(cfg)
    params, _ = m.init(jax.random.key(0))
    return cfg, params


def _reqs(n, rng):
    return [Request(uid=i, prompt=rng.integers(1, 100, size=4).astype(np.int32),
                    max_new_tokens=4) for i in range(n)]


def test_all_requests_complete(setup, rng):
    cfg, params = setup
    eng = Engine(cfg, params, batch_size=2, max_len=32)
    reqs = _reqs(5, rng)                     # 5 requests > 2 slots -> refill
    done = eng.submit_and_run(reqs)
    assert all(r.done for r in done)
    assert all(len(r.out) == 4 for r in done)
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)


def test_greedy_determinism(setup, rng):
    cfg, params = setup
    prompts = _reqs(2, np.random.default_rng(3))
    out1 = Engine(cfg, params, batch_size=2, max_len=32).submit_and_run(
        [Request(r.uid, r.prompt.copy(), r.max_new_tokens) for r in prompts])
    out2 = Engine(cfg, params, batch_size=2, max_len=32).submit_and_run(
        [Request(r.uid, r.prompt.copy(), r.max_new_tokens) for r in prompts])
    assert [r.out for r in out1] == [r.out for r in out2]


def test_quantized_deployment_flow(setup, rng):
    """The paper's pipeline on an LM: train(init) -> PTQ -> serve; the
    quantized engine must produce mostly the same greedy tokens."""
    cfg, params = setup
    qp = ptq.quantize_tree(params)
    deq = ptq.dequantize_tree(qp)
    reqs = _reqs(2, np.random.default_rng(5))
    base = Engine(cfg, params, batch_size=2, max_len=32).submit_and_run(
        [Request(r.uid, r.prompt.copy(), r.max_new_tokens) for r in reqs])
    quant = Engine(cfg, deq, batch_size=2, max_len=32).submit_and_run(
        [Request(r.uid, r.prompt.copy(), r.max_new_tokens) for r in reqs])
    agree = np.mean([a == b for r1, r2 in zip(base, quant)
                     for a, b in zip(r1.out, r2.out)])
    assert agree >= 0.5      # random-init logits are near-ties; int8 stays close


def test_int8_quanttensor_serving_direct(setup, rng):
    """Serve directly from QuantTensor (int8) params — the baked-deployment
    path (dequant-on-use in layers.linear/embed), no dequantized copy."""
    cfg, params = setup
    qp = ptq.quantize_tree(params)
    reqs = [Request(uid=i, prompt=rng.integers(1, 100, size=4).astype(np.int32),
                    max_new_tokens=3) for i in range(2)]
    done = Engine(cfg, qp, batch_size=2, max_len=32).submit_and_run(reqs)
    assert all(r.done and len(r.out) == 3 for r in done)
    assert all(0 <= t < cfg.vocab for r in done for t in r.out)


# ---------------------------------------------------------------------------
# Vision engine: streaming single-image requests over batched backend steps
# ---------------------------------------------------------------------------

from repro.core import backends as B
from repro.core import smallnet
from repro.launch.mesh import make_serving_mesh
from repro.serving.router import FleetExhaustedError, ReplicaRouter
from repro.serving.vision_engine import VisionEngine


@pytest.fixture(scope="module")
def vision_setup(rng):
    params = smallnet.init_params(jax.random.key(0))
    images = rng.uniform(0.0, 1.0, (104, 28, 28, 1)).astype(np.float32)
    return params, images


@pytest.mark.parametrize("backend", B.list_backends())
def test_vision_engine_serves_100_requests(vision_setup, backend):
    """Acceptance: >= 100 queued single-image requests drain through batched
    jitted steps with per-request latency reported, for every registered
    backend, with no batch shed (a faulted step sheds instead of
    raising)."""
    params, images = vision_setup
    eng = VisionEngine(params, backend=backend, batch_size=32)
    res = eng.serve(list(images))
    assert len(res) == 104
    assert [r.uid for r in res] == list(range(104))
    assert all(r.latency_s > 0 for r in res)
    stats = eng.stats()
    assert stats["n"] == 104
    assert stats["shed"] == 0 and stats["accounted"]
    assert stats["batches"] == 4                      # ceil(104/32) batched steps
    assert stats["padded_slots"] == 4 * 32 - 104
    assert stats["latency_p95_ms"] >= stats["latency_p50_ms"] > 0
    assert stats["throughput_qps"] > 0


def test_vision_engine_matches_direct_apply(vision_setup):
    params, images = vision_setup
    eng = VisionEngine(params, backend="ref", batch_size=16)
    res = eng.serve(list(images[:20]))
    direct = smallnet.predict(smallnet.apply(params, jnp.asarray(images[:20]),
                                             backend="ref"))
    assert [r.pred for r in res] == [int(t) for t in direct]
    np.testing.assert_allclose(np.stack([r.scores for r in res]),
                               np.asarray(smallnet.apply(
                                   params, jnp.asarray(images[:20]))),
                               rtol=1e-6, atol=1e-6)


def test_vision_engine_async_submit_then_step(vision_setup):
    """submit() queues without running; step() serves at most one batch."""
    params, images = vision_setup
    eng = VisionEngine(params, backend="ref", batch_size=8)
    uids = [eng.submit(img) for img in images[:11]]
    assert eng.results() == {}                         # nothing served yet
    assert eng.step() == 8                             # first coalesced batch
    assert set(eng.results()) == set(uids[:8])
    assert eng.step() == 3                             # padded remainder batch
    assert eng.step() == 0                             # queue drained
    assert set(eng.results()) == set(uids)


def test_vision_engine_fixed_backend_int_scores(vision_setup):
    params, images = vision_setup
    eng = VisionEngine(params, backend="fixed", batch_size=8)
    res = eng.serve(list(images[:10]))
    assert all(r.scores.dtype == np.int32 for r in res)
    want = smallnet.predict(smallnet.apply(params, jnp.asarray(images[:10]),
                                           backend="fixed"))
    assert [r.pred for r in res] == [int(t) for t in want]


def test_vision_engine_fixed_pallas_serves_bit_exact_words(vision_setup):
    """The fused fixed kernel path through the FULL serving loop (padded
    batches, jitted step) must return the same int32 score words as an
    emulated-fixed engine serving the identical workload."""
    params, images = vision_setup
    res_k = VisionEngine(params, backend="fixed_pallas",
                         batch_size=8).serve(list(images[:20]))
    res_e = VisionEngine(params, backend="fixed",
                         batch_size=8).serve(list(images[:20]))
    assert all(r.scores.dtype == np.int32 for r in res_k)
    np.testing.assert_array_equal(np.stack([r.scores for r in res_k]),
                                  np.stack([r.scores for r in res_e]))
    assert [r.pred for r in res_k] == [r.pred for r in res_e]


# ---------------------------------------------------------------------------
# Engine lifecycle: continuous batching — the intake never closes (regression
# for the old wave model's run()/reopen() churn)
# ---------------------------------------------------------------------------


def test_vision_engine_intake_stays_open_across_drains(vision_setup):
    """run() drains the current queue but the intake stays open: submits
    after a drain serve on the next step, uids keep counting, and the
    served ledger accumulates across bursts."""
    params, images = vision_setup
    eng = VisionEngine(params, backend="ref", batch_size=4, warmup=False)
    eng.submit_many(list(images[:6]))
    assert eng.run() == 6
    res = eng.serve(list(images[6:9]))               # second burst just works
    assert [r.uid for r in res] == [6, 7, 8]
    s = eng.stats()
    assert s["n"] == 9 and s["submitted"] == 9 and s["accounted"]


def test_vision_engine_serving_thread_continuous_batches(vision_setup):
    """start() serves whatever arrives, across separated bursts, with no
    lifecycle calls in between; stop(drain=True) finishes the tail."""
    params, images = vision_setup
    eng = VisionEngine(params, backend="ref", batch_size=4)
    eng.start()
    try:
        uids1 = eng.submit_many(list(images[:5]))
        eng.wait(uids1, timeout=30)
        uids2 = eng.submit_many(list(images[5:8]))   # second burst, same engine
        eng.wait(uids2, timeout=30)
    finally:
        eng.stop()
    res = eng.pop_results(uids1 + uids2)
    assert sorted(res) == sorted(uids1 + uids2)
    assert eng.stats()["accounted"] and eng.stats()["shed"] == 0


# ---------------------------------------------------------------------------
# Mesh-sharded engine: the jitted step splits the batch over the serving mesh
# (degenerate 1-device mesh here; the multi-device case runs in a subprocess)
# ---------------------------------------------------------------------------


def test_vision_engine_sharded_serves_identical_words(vision_setup):
    """A mesh-sharded fixed-point engine must serve the exact int32 score
    words of the unsharded engine (sharding only partitions, never rounds)."""
    params, images = vision_setup
    mesh = make_serving_mesh()
    res_m = VisionEngine(params, backend="fixed", batch_size=8,
                         mesh=mesh).serve(list(images[:20]))
    res_u = VisionEngine(params, backend="fixed",
                         batch_size=8).serve(list(images[:20]))
    np.testing.assert_array_equal(np.stack([r.scores for r in res_m]),
                                  np.stack([r.scores for r in res_u]))
    assert [r.pred for r in res_m] == [r.pred for r in res_u]


def test_vision_engine_pinned_to_one_device(vision_setup):
    """`make_serving_mesh(devices=[d])` pins an engine to device d — how a
    router places one replica per chip — and the Pallas substrate runs
    under the mesh step's shard_map word-for-word."""
    params, images = vision_setup
    d = jax.devices()[-1]
    eng = VisionEngine(params, backend="fixed_pallas", batch_size=8,
                       mesh=make_serving_mesh(devices=[d]))
    assert all(leaf.devices() == {d}
               for leaf in jax.tree_util.tree_leaves(eng.params))
    res = eng.serve(list(images[:12]))
    base = VisionEngine(params, backend="fixed_pallas",
                        batch_size=8).serve(list(images[:12]))
    np.testing.assert_array_equal(np.stack([r.scores for r in res]),
                                  np.stack([r.scores for r in base]))
    assert eng.stats()["accounted"] and eng.stats()["shed"] == 0


def test_vision_engine_sharded_multi_device_subprocess(vision_setup):
    """8 virtual CPU devices: the engine rounds its batch to the mesh
    multiple, serves a ragged workload, and matches the unsharded engine
    word-for-word. Runs in a subprocess so the 1-device default of the rest
    of the suite is untouched."""
    import subprocess
    import sys
    import textwrap
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, numpy as np
        from repro.core import smallnet
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.vision_engine import VisionEngine

        params = smallnet.init_params(jax.random.key(0))
        imgs = np.random.default_rng(0).uniform(
            0, 1, (19, 28, 28, 1)).astype(np.float32)
        mesh = make_serving_mesh()
        assert shd.vision_batch_multiple(mesh) == 8
        eng = VisionEngine(params, backend="fixed", batch_size=6, mesh=mesh)
        assert eng.batch_size == 8          # 6 rounded UP to the mesh multiple
        res = eng.serve(list(imgs))
        base = VisionEngine(params, backend="fixed",
                            batch_size=8).serve(list(imgs))
        ok = (len(res) == 19
              and all((a.scores == b.scores).all() and a.pred == b.pred
                      for a, b in zip(res, base))
              and eng.stats()["mesh_devices"] == 8)
        print(json.dumps({"ok": bool(ok)}))
    """)
    import os
    import pathlib
    src = str(pathlib.Path(__file__).parents[1] / "src")
    env = dict(os.environ,
               PYTHONPATH=src + os.pathsep + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=560, env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    import json as _json
    assert _json.loads(r.stdout.strip().splitlines()[-1])["ok"]


# ---------------------------------------------------------------------------
# Replica router: least-loaded dispatch, failover isolation, fleet stats
# ---------------------------------------------------------------------------


def test_router_two_replicas_per_request_correct(vision_setup):
    """>= 2 replicas drive a workload to completion and every request's
    scores match a direct apply on the backend that served it."""
    params, images = vision_setup
    router = ReplicaRouter.from_backends(params, ["ref", "fixed"],
                                         batch_size=8, warmup=False)
    res = router.serve(list(images[:30]))
    assert len(res) == 30
    assert [r.uid for r in res] == list(range(30))
    names = [eng.backend.name for eng in router.replicas]
    direct = {n: np.asarray(smallnet.apply(params, jnp.asarray(images[:30]),
                                           backend=n)) for n in set(names)}
    for i, r in enumerate(res):
        want = direct[names[r.replica]][i]
        np.testing.assert_allclose(r.scores, want, rtol=1e-6, atol=1e-6)
        assert r.pred == int(np.argmax(want))
    s = router.stats()
    assert s["n"] == 30 and s["healthy"] == 2 and s["failed"] == []
    assert all(v > 0 for v in s["served_by"].values())   # both replicas worked


def test_router_least_loaded_dispatch(vision_setup):
    params, images = vision_setup
    router = ReplicaRouter.from_backends(params, ["ref", "ref", "ref"],
                                         batch_size=8, warmup=False)
    router.submit_many(list(images[:9]))
    assert router.queue_depths() == [3, 3, 3]            # balanced lanes
    # a pre-loaded replica is avoided until the others catch up
    router2 = ReplicaRouter.from_backends(params, ["ref", "ref"],
                                          batch_size=8, warmup=False)
    router2._pending[0] = [None] * 5                     # simulate deep lane
    assigned = [router2._assignment[router2.submit(images[0])]
                for _ in range(5)]
    assert assigned == [1, 1, 1, 1, 1]


def test_router_replica_failure_is_isolated(vision_setup):
    """One replica whose jitted step faults mid-drain must not poison the
    fleet: its requests fail over to the survivor and all complete."""
    params, images = vision_setup
    router = ReplicaRouter.from_backends(params, ["ref", "ref"],
                                         batch_size=8, warmup=False)

    def faulting_step(p, x):
        raise RuntimeError("replica hardware fault")

    router.replicas[0]._step_fn = faulting_step
    uids = router.submit_many(list(images[:20]))
    assert router.run() == 20
    assert set(router.results()) == set(uids)
    s = router.stats()
    assert s["failed"] == [0] and s["healthy"] == 1
    assert s["served_by"] == {0: 0, 1: 20}
    assert isinstance(router.errors()[0], RuntimeError)
    # post-fault submits route around the dead replica
    assert router._assignment[router.submit(images[0])] == 1


def test_router_reclaims_lane_stranded_on_dead_replica(vision_setup):
    """Requests routed to a replica in the window before its fault is
    recorded must fail over at the next run(), not sit on a lane nothing
    drains."""
    params, images = vision_setup
    router = ReplicaRouter.from_backends(params, ["ref", "ref"],
                                         batch_size=8, warmup=False)
    uids = router.submit_many(list(images[:6]))          # balanced 3 / 3
    router._errors[0] = RuntimeError("died before its drain")
    assert router.run() == 6                             # all six served
    assert set(router.results()) == set(uids)
    assert router.stats()["served_by"] == {0: 0, 1: 6}


def test_router_fleet_exhausted_raises(vision_setup):
    params, images = vision_setup
    router = ReplicaRouter.from_backends(params, ["ref"], batch_size=4,
                                         warmup=False)
    router.replicas[0]._step_fn = lambda p, x: (_ for _ in ()).throw(
        RuntimeError("down"))
    router.submit_many(list(images[:4]))
    with pytest.raises(FleetExhaustedError):
        router.run()


def test_router_stats_aggregation(vision_setup):
    """Fleet stats must reconcile with the per-replica engine stats and the
    routed results (latency from ROUTER submit, so >= engine latency)."""
    params, images = vision_setup
    router = ReplicaRouter.from_backends(params, ["ref", "plan"],
                                         batch_size=8, warmup=False)
    res = router.serve(list(images[:24]))
    s = router.stats()
    assert s["n"] == 24 == sum(s["served_by"].values())
    assert sum(p["n"] for p in s["per_replica"]) == 24
    assert s["latency_p95_ms"] >= s["latency_p50_ms"] > 0
    assert s["latency_max_ms"] >= max(r.latency_s for r in res) * 1e3 * (1 - 1e-9)
    assert s["throughput_qps"] > 0
    per_backend = {p["backend"]: p["n"] for p in s["per_replica"]}
    assert per_backend == {"ref": s["served_by"][0], "plan": s["served_by"][1]}
