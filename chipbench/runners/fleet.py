"""Fleet runner: a `ReplicaRouter` over one `VisionEngine` per chip.

Each replica is a continuously batched `VisionEngine` pinned to its own
chip (`make_serving_mesh(devices=[d])`); the router dispatches every
request to a replica by its policy and drains the replicas concurrently.
No admission bound, no deadline, no router SLO, so `failed` counts only
requests that are missing or whose words differ from the reference,
including a result handed back under another request's id.

Traffic parameters (`chipbench/traffic/<mix>.json`):

  replicas        chips, one engine each
  policy          the router's dispatch policy
  outstanding     closed loop: requests kept in flight; a client sends
                  its next request when its last one is answered
  batch, images   as in the classify runner

Throughput counts every request answered from the window's start to the
last answer, over that time.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench import gen, harness
from chipbench import reference as R
from chipbench.runners.classify import compare, control  # noqa: F401
from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.launch.mesh import make_serving_mesh
from repro.serving.router import ReplicaRouter
from repro.serving.vision_engine import VisionEngine


@dataclasses.dataclass
class State:
    cell: harness.Cell
    seed: int
    params: dict
    images: np.ndarray
    router: ReplicaRouter | None


def _counters(router: ReplicaRouter) -> dict:
    out = {"busy_s": 0.0, "batches": 0.0, "padded_slots": 0.0}
    for eng in router.replicas:
        s = eng.stats()
        for k in out:
            out[k] += float(s[k])
    return out


def setup(cell: harness.Cell, seed: int, devices) -> State:
    import jax
    t, c = cell.traffic, cell.config
    cfg = fxp.FixedPointConfig(**c["format"])
    devices = list(devices or jax.devices())[:t["replicas"]]
    if len(devices) < t["replicas"]:
        raise RuntimeError(f"{t['replicas']} replicas need as many devices, "
                           f"found {len(devices)}")
    params = gen.params(seed)
    images = np.stack([gen.image(seed, i) for i in range(t["images"])])
    backend = B.FixedPallasBackend(cfg=cfg)
    router = ReplicaRouter(
        [VisionEngine(params, backend=backend, batch_size=t["batch"],
                      mesh=make_serving_mesh(devices=[d]), max_queue=None,
                      min_step_s=0.0, warmup=True) for d in devices],
        policy=t["policy"])
    router.start()
    st = State(cell, seed, params, images, router)
    _closed_loop(st, 1.0)                 # warm-up: the same loop
    return st


def _closed_loop(st: State, seconds: float):
    """Keep `outstanding` requests in flight for `seconds`; returns
    (t0, sent [(k, uid, t_submit, submit_s)], answered {uid: result})."""
    t = st.cell.traffic
    r, imgs = st.router, st.images
    sent, answered = [], {}
    t0 = time.perf_counter()
    end = t0 + seconds
    k = 0

    def send():
        nonlocal k
        ts = time.perf_counter()
        uid = r.submit(imgs[k % len(imgs)], t_submit=ts)
        sent.append((k, uid, ts, time.perf_counter() - ts))
        k += 1

    for _ in range(t["outstanding"]):
        send()
    while time.perf_counter() < end:
        done = r.pop_results()
        if not done:
            time.sleep(0.0002)
            continue
        answered.update(done)
        for _ in done:
            if time.perf_counter() < end:
                send()
    pending = [uid for _, uid, _, _ in sent if uid not in answered]
    r.wait(pending, timeout=60.0)
    answered.update(r.pop_results(pending))
    r.pop_shed(pending)
    return t0, sent, answered


def window(st: State, seconds: float, trace_dir) -> harness.Window:
    before = _counters(st.router)
    with harness.profiled(trace_dir):
        t0, sent, answered = _closed_loop(st, seconds)
    after = _counters(st.router)
    outputs, lat, t_end = [], [], t0
    by_replica: dict[int, int] = {}
    for k, uid, ts, _ in sent:
        res = answered.get(uid)
        if res is None:
            continue
        outputs.append((k, np.asarray(res.scores), res.pred))
        lat.append((res.t_done - ts) * 1e3)
        t_end = max(t_end, res.t_done)
        by_replica[res.replica] = by_replica.get(res.replica, 0) + 1
    lat = np.asarray(lat)
    d = {k: after[k] - before[k] for k in after}
    d["batch_size"] = float(st.cell.traffic["batch"])
    submit_s = [s for *_, s in sent]
    notes = [
        f"requests sent={len(sent)} answered={len(outputs)} "
        f"by_replica={dict(sorted(by_replica.items()))}",
        f"request_latency_ms p50={np.percentile(lat, 50):.4f} "
        f"p95={np.percentile(lat, 95):.4f} max={lat.max():.4f}"
        if len(lat) else "request_latency_ms none",
        f"engine steps={d['batches']:.0f} busy_s={d['busy_s']:.4f}",
    ]
    return harness.Window(
        metrics={"images_per_s": len(outputs) / (t_end - t0)},
        attempted=len(sent), spans={"submit": submit_s}, counters=d,
        notes=notes, outputs=outputs)


def release(st: State) -> None:
    if st.router is not None:
        st.router.stop(drain=False)
    st.router = None


def check(st: State, w: harness.Window) -> harness.Check:
    ref = R.score_images(st.images, st.params,
                         R.Fmt.of(st.cell.config["format"]))
    return compare(w.outputs, w.attempted, ref)
