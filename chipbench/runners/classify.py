"""Classify runner: single 28x28 images through `VisionEngine`.

Requests go through a continuously batched `VisionEngine` (`smallnet.apply`
on the `fixed_pallas` backend in the configuration's word format: the
`fixed_conv` stage kernels and `fixed_dense`), with no admission bound, no
deadline and no service-time floor, so `failed` counts only requests that
are missing or whose words differ from the reference.

Traffic parameters (`chipbench/traffic/<mix>.json`):

  process, rate   open-loop arrival process and its rate (requests/s);
                  every seed offers round(rate * seconds) requests
  batch           the engine's batch size
  images          distinct images drawn from the seed during set-up;
                  request k carries image k % images

Each request is stamped with its scheduled arrival time, and its latency
runs from there to its result, so a late generator or a stalled engine
shows in the tail. How late the generator ran is printed beside it.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench import gen, harness
from chipbench import reference as R
from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.serving.vision_engine import VisionEngine


@dataclasses.dataclass
class State:
    cell: harness.Cell
    seed: int
    params: dict
    images: np.ndarray
    engine: VisionEngine | None


def _counters(engine: VisionEngine) -> dict:
    s = engine.stats()
    return {k: float(s[k]) for k in ("busy_s", "batches", "padded_slots")}


def setup(cell: harness.Cell, seed: int, devices) -> State:
    t, c = cell.traffic, cell.config
    cfg = fxp.FixedPointConfig(**c["format"])
    params = gen.params(seed)
    images = np.stack([gen.image(seed, i) for i in range(t["images"])])
    engine = VisionEngine(params, backend=B.FixedPallasBackend(cfg=cfg),
                          batch_size=t["batch"], max_queue=None,
                          min_step_s=0.0, warmup=True)
    engine.start()
    # warm-up through the serving thread: full and partial batches
    uids = [engine.submit(images[i % len(images)])
            for i in range(4 * t["batch"] + 3)]
    engine.wait(uids, timeout=120.0)
    engine.pop_results(uids)
    return State(cell, seed, params, images, engine)


def window(st: State, seconds: float, trace_dir) -> harness.Window:
    t = st.cell.traffic
    eng = st.engine
    offsets = gen.arrivals(st.seed, t["process"], t["rate"], seconds)
    imgs = st.images
    before = _counters(eng)
    uids, dues, late = [], [], []
    with harness.profiled(trace_dir):
        t0 = time.perf_counter()
        for k, off in enumerate(offsets):
            due = t0 + off
            now = time.perf_counter()
            while now < due:
                time.sleep(min(0.001, due - now))
                now = time.perf_counter()
            uids.append(eng.submit(imgs[k % len(imgs)], t_submit=due))
            dues.append(due)
            late.append(now - due)
        eng.wait(uids, timeout=60.0 + seconds)
    after = _counters(eng)
    res = eng.pop_results(uids)
    shed = eng.pop_shed(uids)
    lat, t_end = [], t0
    outputs = []
    for k, uid in enumerate(uids):
        r = res.get(uid)
        if r is None:
            continue
        lat.append((r.t_done - dues[k]) * 1e3)
        t_end = max(t_end, r.t_done)
        outputs.append((k, np.asarray(r.scores), r.pred))
    lat = np.asarray(lat)
    late = np.asarray(late) * 1e3
    metrics = {"images_per_s": len(outputs) / (t_end - t0)}
    if len(lat):
        metrics["image_p95_ms"] = float(np.percentile(lat, 95))
    d = {k: after[k] - before[k] for k in after}
    notes = [
        f"requests offered={len(uids)} served={len(outputs)} "
        f"shed={len(shed)}",
        f"generator_late_ms p50={np.percentile(late, 50):.4f} "
        f"p95={np.percentile(late, 95):.4f} max={late.max():.4f}"
        if len(late) else "generator_late_ms none",
        f"request_latency_ms p50={np.percentile(lat, 50):.4f} "
        f"p95={np.percentile(lat, 95):.4f} max={lat.max():.4f}"
        if len(lat) else "request_latency_ms none",
        f"engine steps={d['batches']:.0f} busy_s={d['busy_s']:.4f} "
        f"padded_slots={d['padded_slots']:.0f}",
    ]
    d["batch_size"] = float(t["batch"])
    return harness.Window(metrics=metrics, attempted=len(uids),
                          counters=d, notes=notes, outputs=outputs)


def release(st: State) -> None:
    if st.engine is not None:
        st.engine.stop(drain=False)
    st.engine = None


def compare(outputs, n_offered: int, ref_words: np.ndarray) -> harness.Check:
    """Every served request against the reference of the image it sent:
    the words, and the class the Max Finder picked from them."""
    words_off = requests_off = 0
    n = len(ref_words)
    for k, words, pred in outputs:
        ref = ref_words[k % n]
        bad = np.shape(words) != ref.shape
        if not bad:
            m = int(np.count_nonzero(np.asarray(words) != ref))
            words_off += m
            bad = m > 0 or pred != int(np.argmax(ref))
        requests_off += bad
    missing = n_offered - len(outputs)
    return harness.Check(failed=missing + requests_off, compared=[
        ("requests_missing", missing, 0),
        ("requests_off", requests_off, 0),
        ("score_words_off", words_off, 0)])


def check(st: State, w: harness.Window) -> harness.Check:
    ref = R.score_images(st.images, st.params,
                         R.Fmt.of(st.cell.config["format"]))
    return compare(w.outputs, w.attempted, ref)


def control(cell: harness.Cell, seed: int, n_requests: int) -> harness.Check:
    """The lower-precision control: the reference in `control_format` put
    where the engine's results go, words carried into the configuration's
    units, compared as a run's are."""
    t = cell.traffic
    fmt = R.Fmt.of(cell.config["format"])
    lo = R.Fmt.of(cell.config["control_format"])
    images = np.stack([gen.image(seed, i) for i in range(t["images"])])
    ref = R.score_images(images, gen.params(seed), fmt)
    low = R.score_images(images, gen.params(seed), lo) << (fmt.frac - lo.frac)
    outputs = [(k, low[k % len(low)], int(np.argmax(low[k % len(low)])))
               for k in range(n_requests)]
    return compare(outputs, n_requests, ref)
