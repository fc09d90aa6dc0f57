"""Streaming table: sustained FPS / frame latency / drop rate per substrate.

The paper's real deployment target is frame-rate-bound, not per-image-bound:
this table runs the SAME seeded synthetic clip through the full streaming
pipeline (paced source -> sliding-window tiler -> batched engine waves ->
detections) on every inference substrate and serving topology, and reports

    sustained FPS, p50/p99 frame latency, drop rate, batch occupancy

per row.  Always validated (nonzero exit on failure): every row accounts for
all of its frames (in == served + dropped), and the `ref` backend meets the
FPS target.  `--smoke` trims the sweep for the tier-1 CI lane and adds the
detection assertions: the clip produces a deterministic nonzero detection
count, and `fixed` vs `fixed_pallas` detections are bit-identical.

`--sweep` (implied by `--smoke`) additionally benchmarks the host tiler
against the fully-convolutional frame sweep (`streaming/fcn_sweep.py`) in
THROUGHPUT mode — unpaced, so sustained FPS is the raw pipeline rate, not
the camera clock — at the same stride-8 window lattice, and reports the
speedup per backend.  The smoke lane asserts the two paths' frozen-clip
detections are identical on ref/fixed/fixed_pallas and that the sweep is
STRICTLY faster than the host tiler on `ref` (the whole point of moving
the windowing on device).

The sweep lane also rows the `kernels/frame_trunk` MEGAKERNEL route
(FcnSweep(megakernel=True)) against the composed cascade on both fixed
substrates, with three smoke gates: the megakernel trunk must trace to
exactly ONE `pallas_call` per frame (the composed fixed_pallas cascade to
many), its frozen-clip detections must be bit-identical to the composed
sweep's, and its FPS must hold the perf_ledger band (>= 85% of the
composed sweep measured in the same run).

`--disagg` rows the disaggregated trunk/head fleet (`serving/disagg.py`)
against the monolithic sweep on a query-repetition clip (each frame
queried DISAGG_REPEATS times — the overlapping-window workload the
feature-map cache exists for), on both fixed substrates.  The smoke gates
pin the whole disagg value proposition: window scores word-exact vs the
monolithic sweep, frozen-clip detection parity, measured cache hit rate
above DISAGG_HIT_RATE, disagg FPS at least DISAGG_FPS_GAIN x the
monolithic rate on that clip, and the cached path at least as fast as the
recompute path (an all-distinct clip through a fresh fleet).

`--trace` runs the ref pipeline once more under the span tracer
(`repro/obs`): every frame becomes a `frame` root span with tile/infer/
aggregate children and engine `request`/`device_step` spans below, the
flight-recorder ring is dumped to `<trace-dir>/stream_trace.jsonl` next to
a `metrics.prom` Prometheus exposition, every span is reconciled against
the pipeline AND engine ledgers, and (with `--smoke`) traced FPS must hold
>= 95% of the untraced rate measured in the same process.

    PYTHONPATH=src python -m benchmarks.stream_table --frames 100 --sweep
    PYTHONPATH=src python -m benchmarks.stream_table --frames 30 --smoke
"""
from __future__ import annotations

import argparse
import sys

BACKENDS = ("ref", "pallas", "fixed", "fixed_pallas")
SMOKE_BACKENDS = ("ref", "fixed", "fixed_pallas")
SWEEP_STRIDE = 8               # the sweep lattice: must be a multiple of 4
PARITY_BACKENDS = SMOKE_BACKENDS   # sweep-vs-tiler detection parity set
TRACE_OVERHEAD_BAND = 0.95     # traced FPS must hold >= 95% of untraced
TRACE_CAPACITY = 1 << 16       # flight-recorder ring for the --trace lane
DISAGG_BACKENDS = ("fixed", "fixed_pallas")   # word-exactness substrates
DISAGG_REPEATS = 4             # queries per distinct frame (75% cacheable)
DISAGG_HIT_RATE = 0.5          # measured hit rate floor on the repeated clip
DISAGG_FPS_GAIN = 1.5          # disagg must beat monolithic by this factor


def _params():
    """Seeded params with every leaf nonzero — no training run needed
    (the shared `smallnet.seeded_params` recipe the golden generators and
    frozen-clip tests pin)."""
    from repro.core import smallnet
    return smallnet.seeded_params()


def _calibrated_tiler(params, source, stride: int):
    """Pin the detection threshold to the 80th percentile of the clip's
    first-frame confidences on the "fixed" substrate (the PLAN + Qm.n
    landscape every fixed-point row shares, and a close proxy for the float
    rows), so the sweep always has real detections to aggregate
    (deterministic for a frozen clip)."""
    import numpy as np

    from repro.streaming.tiler import Tiler
    t0 = Tiler(stride=stride)
    tiles, _ = t0.extract(next(iter(source)))
    conf = t0._confidences(t0.score(params, tiles, backend="fixed")).max(-1)
    return Tiler(stride=stride, threshold=float(np.quantile(conf, 0.8)))


def _run_row(params, source, tiler, engine, *, fps: float):
    from repro.streaming.pipeline import StreamConfig, StreamingPipeline
    from repro.streaming.sources import PacedPlayer
    pipe = StreamingPipeline(
        PacedPlayer(source, fps=fps), engine, tiler,
        config=StreamConfig(deadline_ms=3e3 / fps, queue_size=4))
    pipe.run()
    return pipe.stats()


def _sweep_vs_tiler(params, *, frames: int, backends, smoke: bool):
    """Throughput-mode tiler-vs-FCN-sweep pairs on the same stride-8 window
    lattice: rows + failures (smoke gates detection parity and the ref
    speedup)."""
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming.fcn_sweep import FcnSweep
    from repro.streaming.pipeline import StreamingPipeline
    from repro.streaming.sources import SyntheticVideoSource

    source = SyntheticVideoSource(n_frames=frames, seed=7)
    host = _calibrated_tiler(params, source, SWEEP_STRIDE)
    tilers = {"tiler": host,
              "sweep": FcnSweep(stride=SWEEP_STRIDE,
                                threshold=host.threshold)}

    rows, failures = [], []
    for backend in backends:
        fps_by = {}
        for kind, tiler in tilers.items():
            # compile outside the serving clock (the VisionEngine warmup
            # idiom): a one-time trace must not masquerade as steady-state
            # frame cost.  The engine warms its batched step here; sweep
            # pipelines warm their whole-frame program at construction.
            eng = VisionEngine(params, backend=backend, batch_size=64,
                               warmup=(kind == "tiler"))
            # best of 2 runs: the speedup gate compares steady-state rates,
            # and a single run is one scheduler hiccup away from flaking
            best = None
            for _ in range(2):
                pipe = StreamingPipeline(source, eng, tiler)  # throughput
                pipe.run()
                s = pipe.stats()
                if best is None or s["sustained_fps"] > best["sustained_fps"]:
                    best = s
            s = best
            fps_by[kind] = s["sustained_fps"]
            rows.append((
                f"stream/{kind}_{backend}", s.get("latency_p50_ms"),
                f"fps={s['sustained_fps']:.1f} "
                f"p50={s.get('latency_p50_ms', 0):.1f}ms "
                f"p99={s.get('latency_p99_ms', 0):.1f}ms "
                f"drop_rate={s['drop_rate']:.2f} "
                f"served={s['frames_served']}/{s['frames_in']} "
                f"detections={s['detections_total']} "
                f"accounted={'OK' if s['accounted'] else 'FAIL'}"))
            if not s["accounted"]:
                failures.append(f"{kind}_{backend}: unaccounted frames")
        speedup = fps_by["sweep"] / fps_by["tiler"] if fps_by["tiler"] else 0.0
        rows.append((f"stream/sweep_speedup_{backend}", None,
                     f"speedup={speedup:.2f}x tiler={fps_by['tiler']:.1f} "
                     f"sweep={fps_by['sweep']:.1f}"))
        if smoke and backend == "ref" and not fps_by["sweep"] > fps_by["tiler"]:
            failures.append(
                f"FCN sweep is not strictly faster than the host tiler on "
                f"'ref': {fps_by['sweep']:.1f} vs {fps_by['tiler']:.1f} FPS")

    if smoke:
        clip = SyntheticVideoSource(n_frames=min(frames, 8), seed=7).frames()
        for backend in PARITY_BACKENDS:
            dt = [tilers["tiler"].detect(params, f, backend=backend)
                  for f in clip]
            ds = [tilers["sweep"].detect(params, f, backend=backend)
                  for f in clip]
            n = sum(len(d) for d in dt)
            # the fixed substrates are word-exact by construction, so their
            # Detections (float scores included) must be identical; float
            # backends carry ~1-ulp conv summation-order latitude, so the
            # gate there is labels/positions exact + scores within 1e-5
            # (a jaxlib upgrade must not redden the smoke on correct code)
            exact = backend in ("fixed", "fixed_pallas")
            ok = all(_same_detections(a, b, exact) for a, b in zip(dt, ds))
            rows.append((f"stream/sweep_parity_{backend}", None,
                         f"n={n} frames={len(clip)} "
                         f"identical={'OK' if ok else 'FAIL'}"))
            if not ok:
                diff = sum(not _same_detections(a, b, exact)
                           for a, b in zip(dt, ds))
                failures.append(f"sweep vs tiler detections differ on "
                                f"{diff}/{len(clip)} frames ({backend})")
            if backend == "fixed" and n == 0:
                failures.append("sweep parity clip produced zero detections")
    return rows, failures


def _megakernel_rows(params, *, frames: int, smoke: bool):
    """Composed-cascade vs one-launch-megakernel sweep rows on the fixed
    substrates: launch topology (static jaxpr counts), frozen-clip
    detection parity, and the in-run FPS band — the stream-side view of
    what benchmarks/perf_ledger.py persists."""
    import jax.numpy as jnp

    from benchmarks.perf_ledger import FPS_BAND, MEGA_BACKENDS
    from repro.analysis.launches import count_pallas_launches
    from repro.core import backends as B
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming import fcn_sweep as fs
    from repro.streaming.fcn_sweep import FcnSweep
    from repro.streaming.pipeline import StreamingPipeline
    from repro.streaming.sources import SyntheticVideoSource

    source = SyntheticVideoSource(n_frames=frames, seed=7)
    host = _calibrated_tiler(params, source, SWEEP_STRIDE)
    H, W = source.frame_shape
    probe = jnp.zeros((1, H, W, 1), jnp.float32)

    rows, failures = [], []
    for backend in MEGA_BACKENDS:
        be = B.get_backend(backend)
        p = be.prepare_params(params)
        launches = {mega: count_pallas_launches(
            lambda f: fs._trunk_quad(be, p, f, mega), probe)
            for mega in (False, True)}
        fps_by, det_by = {}, {}
        for kind, mega in (("composed", False), ("mega", True)):
            tiler = FcnSweep(stride=SWEEP_STRIDE, threshold=host.threshold,
                             megakernel=mega)
            eng = VisionEngine(params, backend=backend, batch_size=64,
                               warmup=False)
            best = None            # best of 2, as in _sweep_vs_tiler
            for _ in range(2):
                pipe = StreamingPipeline(source, eng, tiler)
                pipe.run()
                s = pipe.stats()
                if best is None or s["sustained_fps"] > best["sustained_fps"]:
                    best = s
            fps_by[kind] = best["sustained_fps"]
            clip = SyntheticVideoSource(n_frames=min(frames, 8),
                                        seed=7).frames()
            det_by[kind] = [tiler.detect(params, f, backend=backend)
                            for f in clip]
            rows.append((
                f"stream/{kind}_trunk_{backend}",
                best.get("latency_p50_ms"),
                f"fps={best['sustained_fps']:.1f} "
                f"p50={best.get('latency_p50_ms', 0):.1f}ms "
                f"p99={best.get('latency_p99_ms', 0):.1f}ms "
                f"drop_rate={best['drop_rate']:.2f} "
                f"trunk_launches/frame={launches[mega]}"))
        ratio = fps_by["mega"] / fps_by["composed"] if fps_by["composed"] else 0
        parity = det_by["mega"] == det_by["composed"]
        rows.append((f"stream/mega_vs_composed_{backend}", None,
                     f"fps_ratio={ratio:.2f} launches "
                     f"{launches[False]}->{launches[True]} "
                     f"detections_identical={'OK' if parity else 'FAIL'}"))
        if smoke:
            if launches[True] != 1:
                failures.append(
                    f"megakernel trunk on '{backend}' traces to "
                    f"{launches[True]} pallas_calls per frame, not 1")
            if backend == "fixed_pallas" and launches[False] <= 1:
                failures.append(
                    "composed fixed_pallas cascade unexpectedly traces to "
                    f"{launches[False]} launches — the megakernel row is "
                    "no longer measuring a fusion")
            if not parity:
                diff = sum(a != b for a, b in
                           zip(det_by["mega"], det_by["composed"]))
                failures.append(
                    f"megakernel vs composed sweep detections differ on "
                    f"{diff} frames ({backend}) — word-exactness broke")
            if fps_by["mega"] < FPS_BAND * fps_by["composed"]:
                failures.append(
                    f"megakernel sweep on '{backend}' fell past the "
                    f"{FPS_BAND:.0%} FPS band: {fps_by['mega']:.1f} vs "
                    f"composed {fps_by['composed']:.1f}")
    return rows, failures


def _trace_rows(params, *, frames: int, smoke: bool, trace_dir: str):
    """Traced-vs-untraced overhead + span/ledger reconciliation rows.

    Runs the ref-backend throughput pipeline best-of-2 per side (the same
    flake armour as the sweep gates): first with the tracer disabled, then
    with a fresh flight recorder per repetition.  The best traced rep's
    spans must reconcile with BOTH ledgers of the same run — the pipeline
    (one terminal `frame` root per frame, counts equal to served/dropped)
    and the engine (`request` roots vs served + shed) — and under --smoke
    traced FPS must hold >= TRACE_OVERHEAD_BAND of the untraced rate
    measured in the same process.  Artifacts land in `trace_dir`:
    stream_trace.jsonl (flight-recorder dump, header line + one span per
    line) and metrics.prom (Prometheus exposition of the whole registry).
    """
    import gc
    import os

    from repro.obs import recorder as R
    from repro.obs import trace as T
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming.pipeline import StreamingPipeline
    from repro.streaming.sources import SyntheticVideoSource

    source = SyntheticVideoSource(n_frames=frames, seed=7)
    tiler = _calibrated_tiler(params, source, SWEEP_STRIDE)

    def one_run():
        eng = VisionEngine(params, backend="ref", batch_size=64)
        pipe = StreamingPipeline(source, eng, tiler)     # throughput mode
        pipe.run()
        return pipe.stats()

    rows, failures = [], []
    # Overhead methodology: single-run FPS on a shared CI box swings far
    # more than the ~1-2% the tracer actually costs, so the comparison
    #   - POOLS wall time over N reps per side (pooled fps = frames/wall;
    #     variance shrinks with N where single-pair ratios don't),
    #   - ALTERNATES side order between pairs (off,on / on,off) so slow
    #     drift cancels instead of biasing whichever side runs second,
    #   - pins the GC during every measured rep, both sides equally (the
    #     pyperf idiom: collection pauses land on whichever run happens
    #     to cross a threshold, which reads as fake overhead),
    #   - and on a failing band DOUBLES the rep count once before calling
    #     it — a real regression stays slow on every extra rep.
    T.disable()
    one_run()                                     # warm the jitted step
    wall = {False: 0.0, True: 0.0}                # traced? -> total seconds
    frames_by = {False: 0, True: 0}
    best = None
    n_reps = 0

    def measured(traced):
        nonlocal best, n_reps
        n_reps += traced
        if traced:
            tr = T.enable(capacity=TRACE_CAPACITY, dump_dir=trace_dir)
        else:
            T.disable()
        gc.collect()
        gc.disable()
        try:
            s = one_run()
        finally:
            gc.enable()
        wall[traced] += s["frames_in"] / s["sustained_fps"]
        frames_by[traced] += s["frames_in"]
        if traced and (best is None
                       or s["sustained_fps"] > best[0]["sustained_fps"]):
            best = (s, tr.recorder.spans(), tr.recorder)

    def pooled_ratio():
        fps_off = frames_by[False] / wall[False]
        fps_on = frames_by[True] / wall[True]
        return fps_on / fps_off, fps_off, fps_on

    # Up to 3 independent 4-pair windows, best window wins: a burst that
    # pollutes one window must not be merged into the next (the estimates
    # stay independent), and a REAL regression fails every window while
    # noise has to get unlucky three times in a row.
    ratio, fps_off, fps_on = 0.0, 0.0, 0.0
    for window in range(3):
        wall.update({False: 0.0, True: 0.0})
        frames_by.update({False: 0, True: 0})
        for rep in range(4):
            first = rep % 2 == 0
            measured(first)
            measured(not first)
        r = pooled_ratio()
        if r[0] > ratio:
            ratio, fps_off, fps_on = r
        if not smoke or ratio >= TRACE_OVERHEAD_BAND:
            break
    T.disable()
    s, spans, rec = best

    rows.append(("stream/trace_overhead", None,
                 f"untraced_fps={fps_off:.1f} traced_fps={fps_on:.1f} "
                 f"ratio={ratio:.3f} reps={n_reps}x2 "
                 f"band={TRACE_OVERHEAD_BAND:.2f} "
                 f"gated={'yes' if smoke else 'no'}"))
    if smoke and ratio < TRACE_OVERHEAD_BAND:
        failures.append(
            f"tracing overhead exceeds the {1 - TRACE_OVERHEAD_BAND:.0%} "
            f"band: pooled traced/untraced FPS ratio {ratio:.3f} "
            f"({fps_on:.1f} vs {fps_off:.1f} over {n_reps} reps per side)")

    if rec.evicted:
        failures.append(
            f"flight recorder evicted {rec.evicted} spans during the traced "
            f"run — raise TRACE_CAPACITY; reconciliation needs the full run")
    fails = R.reconcile(spans, frames_served=s["frames_served"],
                        frames_dropped=s["frames_dropped"])
    es = s["engine"]
    fails += R.reconcile(spans, served=es["n"], shed=es["shed"],
                         root_name="request")
    rows.append(("stream/trace_reconcile", None,
                 f"spans={len(spans)} frames={s['frames_in']} "
                 f"requests={es['submitted']} "
                 f"reconciled={'OK' if not fails else 'FAIL'}"))
    failures += [f"trace reconcile: {f}" for f in fails]

    jsonl = rec.dump_jsonl(os.path.join(trace_dir, "stream_trace.jsonl"),
                           reason="stream_table",
                           detail=f"frames={frames} backend=ref")
    prom = R.dump_prometheus(os.path.join(trace_dir, "metrics.prom"))
    rows.append(("stream/trace_artifacts", None,
                 f"jsonl={jsonl} prom={prom} spans={len(spans)}"))
    return rows, failures


def _disagg_rows(params, *, frames: int, smoke: bool):
    """Monolithic-sweep vs disaggregated trunk/head serving on a
    query-repetition clip (every frame queried DISAGG_REPEATS times — the
    overlapping-window workload `serving/disagg.py` exists for).

    Per fixed substrate, all best-of-2: the monolithic `FcnSweep.score`
    loop (recomputes the fused trunk+head program per query), the disagg
    `score_frame` loop on the same repeated clip (fresh server per rep, so
    the hit rate is the workload's, not an artifact of a pre-warmed
    cache), and the disagg loop on the all-distinct base clip (the
    recompute path — every query a cache miss).  The serving lanes are
    driven DIRECTLY (not through `StreamingPipeline`): the speedup gate
    compares serving cost, and the pipeline's fixed ~1 ms/frame of asyncio
    scheduling would otherwise dilute both sides equally and hide the
    ratio.  A separate pipeline-driven row proves the wiring (the disagg
    server slots in where the sweep does) and gates accounting only.

    Smoke gates: window scores word-exact vs the monolithic sweep,
    frozen-clip detection parity, measured hit rate above DISAGG_HIT_RATE,
    disagg FPS >= DISAGG_FPS_GAIN x monolithic on the repeated clip,
    cached-path FPS >= recompute-path FPS, and every ledger accounted."""
    import time

    import jax
    import numpy as np

    from repro.serving.disagg import DisaggServer
    from repro.streaming.fcn_sweep import FcnSweep
    from repro.streaming.pipeline import StreamingPipeline
    from repro.streaming.sources import (RepeatedClipSource,
                                         SyntheticVideoSource)

    distinct = max(2, frames // DISAGG_REPEATS)
    base = SyntheticVideoSource(n_frames=distinct, seed=7)
    repeated = RepeatedClipSource(base, repeats=DISAGG_REPEATS)
    rep_px = [f.pixels[None] for f in repeated.frames()]
    base_px = [f.pixels[None] for f in base.frames()]
    host = _calibrated_tiler(params, base, SWEEP_STRIDE)

    rows, failures = [], []
    for backend in DISAGG_BACKENDS:
        sweep = FcnSweep(stride=SWEEP_STRIDE, threshold=host.threshold)

        def mono_run():
            t0 = time.perf_counter()
            for px in rep_px:
                jax.block_until_ready(sweep.score(params, px,
                                                  backend=backend))
            return len(rep_px) / (time.perf_counter() - t0)

        def disagg_run(clip_px):
            # fresh server per rep: the measured hit rate is what THIS
            # clip earns, and construction (compile + warmup) stays
            # outside the measured window
            srv = DisaggServer(params, backend=backend,
                               frame_shape=base.frame_shape,
                               stride=SWEEP_STRIDE,
                               cache_capacity=distinct + 2)
            t0 = time.perf_counter()
            for px in clip_px:
                srv.score_frame(px)
            return len(clip_px) / (time.perf_counter() - t0), srv.stats()

        jax.block_until_ready(sweep.score(params, rep_px[0],
                                          backend=backend))   # compile
        mono_fps = max(mono_run() for _ in range(2))
        dis_fps, dis_d = max((disagg_run(rep_px) for _ in range(2)),
                             key=lambda fd: fd[0])
        rec_fps, rec_d = max((disagg_run(base_px) for _ in range(2)),
                             key=lambda fd: fd[0])

        # pipeline wiring row: the disagg server driven exactly where the
        # monolithic sweep runs (accounting gated; FPS informational —
        # the asyncio harness cost dominates at smallNet per-frame scale)
        pipe_srv = DisaggServer(params, backend=backend,
                                frame_shape=base.frame_shape,
                                stride=SWEEP_STRIDE,
                                cache_capacity=distinct + 2)
        pipe = StreamingPipeline(repeated, pipe_srv, sweep)
        pipe.run()
        pipe_s, pipe_d = pipe.stats(), pipe_srv.stats()

        rows.append((
            f"stream/disagg_mono_{backend}", None,
            f"fps={mono_fps:.1f} queries={len(rep_px)} "
            f"repeats={DISAGG_REPEATS}"))
        cache = dis_d["cache"]
        rows.append((
            f"stream/disagg_{backend}", None,
            f"fps={dis_fps:.1f} served={dis_d['n']}/{dis_d['submitted']} "
            f"hit_rate={cache['hit_rate']:.2f} "
            f"hits={cache['hits']} misses={cache['misses']} "
            f"trunk={dis_d['topology']['trunk']} "
            f"head={dis_d['topology']['head']} "
            f"accounted={'OK' if dis_d['accounted'] else 'FAIL'}"))
        speedup = dis_fps / mono_fps if mono_fps else 0.0
        cached_vs_rec = dis_fps / rec_fps if rec_fps else 0.0
        rows.append((
            f"stream/disagg_speedup_{backend}", None,
            f"vs_mono={speedup:.2f}x mono={mono_fps:.1f} "
            f"disagg={dis_fps:.1f} recompute={rec_fps:.1f} "
            f"cached_vs_recompute={cached_vs_rec:.2f}x"))
        rows.append((
            f"stream/disagg_pipeline_{backend}",
            pipe_s.get("latency_p50_ms"),
            f"fps={pipe_s['sustained_fps']:.1f} "
            f"served={pipe_s['frames_served']}/{pipe_s['frames_in']} "
            f"hit_rate={pipe_d['cache']['hit_rate']:.2f} "
            f"accounted="
            f"{'OK' if pipe_s['accounted'] and pipe_d['accounted'] else 'FAIL'}"))

        if not (dis_d["accounted"] and rec_d["accounted"]
                and pipe_s["accounted"] and pipe_d["accounted"]):
            failures.append(f"disagg_{backend}: unaccounted frames/queries")
        if smoke and pipe_s["frames_served"] != pipe_s["frames_in"]:
            failures.append(
                f"disagg pipeline on '{backend}' dropped "
                f"{pipe_s['frames_dropped']} of {pipe_s['frames_in']} "
                f"frames in throughput mode")
        if not smoke:
            continue
        # word-exactness: the disagg chain (trunk pool -> cache -> head
        # pool) must reproduce the monolithic sweep's window-score words
        # exactly on the fixed substrates — same ints, same dtype
        clip = base.frames()[:4]
        for f in clip:
            a = np.asarray(sweep.score(params, f.pixels[None],
                                       backend=backend))
            srv = DisaggServer(params, backend=backend,
                               frame_shape=base.frame_shape,
                               stride=SWEEP_STRIDE,
                               cache_capacity=distinct + 2)
            b = np.asarray(srv.score_frame(f.pixels[None]))
            if a.dtype != b.dtype or not np.array_equal(a, b):
                failures.append(
                    f"disagg scores not word-exact vs monolithic sweep on "
                    f"'{backend}' frame {f.index} "
                    f"(dtype {a.dtype} vs {b.dtype})")
                break
            dt = sweep.aggregate(a, list(srv.positions))
            dd = srv.detect(f, tiler=sweep)
            if dt != dd:
                failures.append(
                    f"disagg vs monolithic detections differ on "
                    f"'{backend}' frame {f.index}")
                break
        if cache["hit_rate"] <= DISAGG_HIT_RATE:
            failures.append(
                f"disagg cache hit rate {cache['hit_rate']:.2f} on the "
                f"repeated clip ({backend}) is not above "
                f"{DISAGG_HIT_RATE:.0%}")
        if dis_fps < DISAGG_FPS_GAIN * mono_fps:
            failures.append(
                f"disagg on '{backend}' fell short of "
                f"{DISAGG_FPS_GAIN:g}x monolithic on the repeated clip: "
                f"{dis_fps:.1f} vs {mono_fps:.1f} FPS")
        if dis_fps < rec_fps:
            failures.append(
                f"cached path on '{backend}' is slower than the recompute "
                f"path: {dis_fps:.1f} vs {rec_fps:.1f} FPS — the cache is "
                f"costing more than the trunk it skips")
    return rows, failures


def _same_detections(a, b, exact: bool) -> bool:
    """Frame detection-list parity: strict equality for the word-exact
    fixed substrates, float-tolerant scores for the float backends."""
    if exact:
        return a == b
    return len(a) == len(b) and all(
        da.label == db.label and da.y == db.y and da.x == db.x
        and da.size == db.size and abs(da.score - db.score) <= 1e-5
        for da, db in zip(a, b))


def run(*, frames: int, fps: float, stride: int, smoke: bool,
        sweep: bool = False, trace: bool = False,
        trace_dir: str = "traces", disagg: bool = False):
    """Returns (rows, failures).  Rows follow the benchmarks CSV contract."""
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.router import ReplicaRouter
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming.sources import SyntheticVideoSource

    params = _params()
    source = SyntheticVideoSource(n_frames=frames, seed=7)
    tiler = _calibrated_tiler(params, source, stride)
    n_tiles = len(tiler.positions(source.frame_shape))

    rows, failures = [], []
    rows.append(("stream/clip", None,
                 f"frames={frames} shape={source.frame_shape} "
                 f"tiles/frame={n_tiles} stride={stride} "
                 f"threshold={tiler.threshold:.4f} target_fps={fps:g}"))

    def engine_for(backend):
        return VisionEngine(params, backend=backend, batch_size=64)

    names = SMOKE_BACKENDS if smoke else BACKENDS
    topologies = {} if smoke else {
        "topology_sharded": lambda: VisionEngine(
            params, backend="ref", batch_size=64, mesh=make_serving_mesh()),
        "topology_routed_x2": lambda: ReplicaRouter.from_backends(
            params, ["ref", "ref"], batch_size=64),
    }
    sweeps = {f"backend_{n}": (lambda n=n: engine_for(n)) for n in names}
    sweeps.update(topologies)

    for label, build in sweeps.items():
        s = _run_row(params, source, tiler, build(), fps=fps)
        occ = s.get("batch_occupancy")
        occ_s = f"{occ:.2f}" if occ is not None else "n/a"
        rows.append((
            f"stream/{label}", s.get("latency_p50_ms"),
            f"fps={s['sustained_fps']:.1f} p50={s.get('latency_p50_ms', 0):.1f}ms "
            f"p99={s.get('latency_p99_ms', 0):.1f}ms "
            f"drop_rate={s['drop_rate']:.2f} occupancy={occ_s} "
            f"served={s['frames_served']}/{s['frames_in']} "
            f"detections={s['detections_total']} "
            f"accounted={'OK' if s['accounted'] else 'FAIL'}"))
        if not s["accounted"]:
            failures.append(f"{label}: {s['frames_in']} frames in != "
                            f"{s['frames_served']} served + "
                            f"{s['frames_dropped']} dropped")
        if label == "backend_ref":
            # the frame-rate target every future perf PR measures against
            if s["sustained_fps"] < 0.8 * fps:
                failures.append(f"ref backend misses the {fps:g} FPS target: "
                                f"sustained {s['sustained_fps']:.1f}")
            if s["drop_rate"] >= 1.0:
                failures.append("ref backend dropped every frame")

    if smoke:
        failures += _detection_smoke(params, tiler, frames=min(frames, 10))
    if sweep or smoke:
        srows, sfail = _sweep_vs_tiler(
            params, frames=min(frames, 20),
            backends=("ref",) if smoke else names, smoke=smoke)
        rows += srows
        failures += sfail
        mrows, mfail = _megakernel_rows(
            params, frames=min(frames, 20), smoke=smoke)
        rows += mrows
        failures += mfail
    if disagg:
        drows, dfail = _disagg_rows(
            params, frames=min(frames, 24), smoke=smoke)
        rows += drows
        failures += dfail
    if trace:
        trows, tfail = _trace_rows(
            params, frames=min(frames, 30), smoke=smoke,
            trace_dir=trace_dir)
        rows += trows
        failures += tfail
    return rows, failures


def _detection_smoke(params, tiler, *, frames: int) -> list[str]:
    """Frozen-clip detection assertions for the CI lane: nonzero count, and
    bit-identical output between the two fixed-point substrates."""
    from repro.streaming.sources import SyntheticVideoSource
    clip = SyntheticVideoSource(n_frames=frames, seed=7).frames()
    det_f = [tiler.detect(params, f, backend="fixed") for f in clip]
    det_fp = [tiler.detect(params, f, backend="fixed_pallas") for f in clip]
    failures = []
    n = sum(len(d) for d in det_f)
    if n == 0:
        failures.append("frozen clip produced zero detections on 'fixed'")
    if det_f != det_fp:
        diff = sum(a != b for a, b in zip(det_f, det_fp))
        failures.append(f"fixed vs fixed_pallas detections differ on "
                        f"{diff}/{frames} frames")
    print(f"stream/detection_smoke,,n={n} frames={frames} "
          f"bitexact={'OK' if det_f == det_fp else 'FAIL'}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=100)
    ap.add_argument("--fps", type=float, default=10.0,
                    help="paced source frame rate (the real-time target)")
    ap.add_argument("--stride", type=int, default=14,
                    help="sliding-window stride over the frame")
    ap.add_argument("--smoke", action="store_true",
                    help="trimmed sweep + detection assertions (CI tier-1); "
                         "implies --sweep for the ref backend")
    ap.add_argument("--sweep", action="store_true",
                    help="add throughput-mode tiler-vs-FCN-sweep comparison "
                         "rows (speedup per backend)")
    ap.add_argument("--trace", action="store_true",
                    help="run the ref pipeline under the span tracer: emits "
                         "stream_trace.jsonl + metrics.prom under "
                         "--trace-dir, reconciles every frame against the "
                         "pipeline/engine ledgers, and (with --smoke) gates "
                         "traced FPS >= 95%% of untraced")
    ap.add_argument("--trace-dir", default="traces",
                    help="directory for --trace artifacts")
    ap.add_argument("--disagg", action="store_true",
                    help="add disaggregated trunk/head serving rows on a "
                         "query-repetition clip: monolithic vs disagg FPS, "
                         "cache hit rate, and (with --smoke) the "
                         "word-exactness / parity / hit-rate / speedup "
                         "gates")
    args = ap.parse_args()
    from repro.core import runtime
    runtime.init_compile_cache()

    print("name,us_per_call,derived")
    rows, failures = run(frames=args.frames, fps=args.fps,
                         stride=args.stride, smoke=args.smoke,
                         sweep=args.sweep, trace=args.trace,
                         trace_dir=args.trace_dir, disagg=args.disagg)
    for name, val, derived in rows:
        val_s = f"{val:.2f}" if val is not None else ""
        print(f"{name},{val_s},{derived}")
    for f in failures:
        print(f"stream/FAIL,,{f}")
    print(f"stream/result,,{'FAIL' if failures else 'OK'}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
