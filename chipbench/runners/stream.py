"""Stream runner: one camera or one unpaced stream through the pipeline.

Frames go through `StreamingPipeline` -> `FcnSweep.score` on the
`fixed_pallas` backend in the configuration's word format (the one-launch
`frame_trunk` megakernel, the window gather and `fixed_dense`), then the
pipeline's aggregate stage turns scores into detections.

Traffic parameters (`chipbench/traffic/<mix>.json`):

  height, width   frame size in pixels
  fps             paced camera rate; null streams as fast as the pipeline
                  takes frames (ingest blocks, nothing is dropped)
  stride          window lattice of the sweep (a multiple of 4)
  digits          glyphs drawn on each frame
  ring            distinct frames drawn from the seed during set-up; the
                  window cycles through them
  threshold, min_dist, queue_size   the detector's and the pipeline's
                  settings

Frame i is due at t0 + i/fps (paced) or when the source hands it over
(unpaced); its latency runs from that due time to its detections, so a
stall shows in the tail instead of as a drop. The pipeline runs with no
deadline and blocking ingest, so `failed` counts only frames that are
missing or whose words or detections differ from the reference.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import time

import numpy as np

from chipbench import gen, harness
from chipbench import reference as R
from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.serving.vision_engine import VisionEngine
from repro.streaming.fcn_sweep import FcnSweep
from repro.streaming.pipeline import StreamConfig, StreamingPipeline
from repro.streaming.sources import Frame


class Log:
    """Per-frame stage times, and the scores the aggregate stage saw."""

    def __init__(self):
        self.traced = False
        self.reset()

    def reset(self):
        self.times = {"extract": [], "score": [], "aggregate": []}
        self.scores: list[np.ndarray] = []

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(f"bench.{name}")


@dataclasses.dataclass(frozen=True)
class RecordingSweep(FcnSweep):
    """`FcnSweep` with the harness's spans around the three stage calls
    the pipeline makes. It records the scores handed to `aggregate`, in
    the order the pipeline serves frames; the program's code runs as is."""
    log: Log = dataclasses.field(default=None, compare=False, hash=False)

    def extract(self, frame):
        t0 = time.perf_counter()
        with self.log.span("extract"):
            out = super().extract(frame)
        self.log.times["extract"].append(time.perf_counter() - t0)
        return out

    def score(self, params, frames, *, backend="ref"):
        t0 = time.perf_counter()
        with self.log.span("score"):
            out = super().score(params, frames, backend=backend)
        self.log.times["score"].append(time.perf_counter() - t0)
        return out

    def aggregate(self, scores, positions, tiles=None):
        t0 = time.perf_counter()
        with self.log.span("aggregate"):
            out = super().aggregate(scores, positions, tiles)
        self.log.times["aggregate"].append(time.perf_counter() - t0)
        self.log.scores.append(scores)
        return out


class Source:
    """The frame schedule: frame i of the ring, due at t0 + i/fps."""

    def __init__(self, ring, fps, seconds):
        self.ring = ring
        self.fps = fps
        self.seconds = seconds
        self.t0 = None
        self.due: list[float] = []
        self.late: list[float] = []

    def __aiter__(self):
        return self._gen()

    async def _gen(self):
        self.t0 = t0 = time.perf_counter()
        end = t0 + self.seconds
        i = 0
        while True:
            if self.fps:
                due = t0 + i / self.fps
                if due >= end:
                    return
                delay = due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
            else:
                due = time.perf_counter()
                if due >= end:
                    return
            self.due.append(due)
            self.late.append(time.perf_counter() - due)
            yield Frame(index=i, pixels=self.ring[i % len(self.ring)],
                        truth=[], t_source=due)
            i += 1



@dataclasses.dataclass
class State:
    cell: harness.Cell
    params: dict
    ring: list
    tiler: RecordingSweep
    engine: object
    log: Log


def setup(cell: harness.Cell, seed: int, devices) -> State:
    t, c = cell.traffic, cell.config
    cfg = fxp.FixedPointConfig(**c["format"])
    backend = B.FixedPallasBackend(cfg=cfg)
    params = gen.params(seed)
    ring = [gen.frame(seed, i, t["height"], t["width"], t["digits"])[..., None]
            for i in range(t["ring"])]
    log = Log()
    tiler = RecordingSweep(stride=t["stride"], threshold=t["threshold"],
                           min_dist=t["min_dist"], cfg=cfg, log=log)
    engine = VisionEngine(params, backend=backend, batch_size=1,
                          warmup=False)
    st = State(cell, params, ring, tiler, engine, log)
    # warm-up: every ring frame once through the same pipeline, unpaced
    _run_pipeline(st, _Finite(ring))
    return st


class _Finite:
    def __init__(self, ring):
        self.ring = ring

    def __aiter__(self):
        return self._gen()

    async def _gen(self):
        for i, px in enumerate(self.ring):
            yield Frame(index=i, pixels=px, truth=[], t_source=0.0)


def _run_pipeline(st: State, source):
    t = st.cell.traffic
    pipe = StreamingPipeline(
        source, st.engine, st.tiler,
        config=StreamConfig(deadline_ms=None, queue_size=t["queue_size"],
                            realtime=False))
    return pipe.run()


def window(st: State, seconds: float, trace_dir) -> harness.Window:
    t = st.cell.traffic
    st.log.reset()
    st.log.traced = trace_dir is not None
    src = Source(st.ring, t["fps"], seconds)
    with harness.profiled(trace_dir):
        results = _run_pipeline(st, src)
    st.log.traced = False
    n_due = len(src.due)
    t_end = max((r.t_done for r in results), default=src.t0)
    lat = np.asarray([(r.t_done - src.due[r.index]) * 1e3 for r in results])
    late = np.asarray(src.late) * 1e3
    metrics = {"frames_per_s": len(results) / (t_end - src.t0)}
    if len(lat):
        metrics["frame_p95_ms"] = float(np.percentile(lat, 95))
    times = st.log.times
    notes = [
        f"frames due={n_due} served={len(results)}",
        f"generator_late_ms p50={np.percentile(late, 50):.4f} "
        f"p95={np.percentile(late, 95):.4f} max={late.max():.4f}"
        if len(late) else "generator_late_ms none",
        f"frame_latency_ms p50={np.percentile(lat, 50):.4f} "
        f"p95={np.percentile(lat, 95):.4f} max={lat.max():.4f}"
        if len(lat) else "frame_latency_ms none",
    ]
    outputs = [(r.index, words, [(d.label, d.score, d.y, d.x)
                                 for d in r.detections])
               for r, words in zip(results, st.log.scores)]
    if len(st.log.scores) != len(results):
        notes.append(f"recorded {len(st.log.scores)} score arrays for "
                     f"{len(results)} served frames")
        outputs = [(r.index, None, []) for r in results]
    return harness.Window(metrics=metrics, attempted=n_due, spans=times,
                          notes=notes, outputs=outputs)


def release(st: State) -> None:
    st.engine = None
    st.tiler = None


def reference(cell: harness.Cell, params, ring, fmt: R.Fmt):
    """Words and detections of every ring frame, by the plain reference
    in the word format `fmt`."""
    t = cell.traffic
    pos = R.window_positions(t["height"], t["width"], t["stride"])
    words = [R.score_frame(px[..., 0], params, fmt, pos) for px in ring]
    dets = [R.detections(w, pos, fmt, threshold=t["threshold"],
                         min_dist=t["min_dist"]) for w in words]
    return words, dets


def compare(outputs, n_due: int, ref_words, ref_dets) -> harness.Check:
    """Every served frame against the reference of its ring slot."""
    words_off = frames_off = 0
    for index, words, dets in outputs:
        slot = index % len(ref_words)
        bad = words is None or words.shape != ref_words[slot].shape
        if not bad:
            n = int(np.count_nonzero(np.asarray(words) != ref_words[slot]))
            words_off += n
            bad = n > 0 or dets != ref_dets[slot]
        frames_off += bad
    missing = n_due - len(outputs)
    return harness.Check(failed=missing + frames_off, compared=[
        ("frames_missing", missing, 0),
        ("frames_off", frames_off, 0),
        ("score_words_off", words_off, 0)])


def check(st: State, w: harness.Window) -> harness.Check:
    ref_words, ref_dets = reference(st.cell, st.params, st.ring,
                                    R.Fmt.of(st.cell.config["format"]))
    return compare(w.outputs, w.attempted, ref_words, ref_dets)


def control(cell: harness.Cell, seed: int, n_frames: int) -> harness.Check:
    """The lower-precision control: the reference in the configuration's
    `control_format` put where the program's outputs go, its words carried
    into the configuration's units, compared as a run's are."""
    t = cell.traffic
    fmt = R.Fmt.of(cell.config["format"])
    lo = R.Fmt.of(cell.config["control_format"])
    params = gen.params(seed)
    ring = [gen.frame(seed, i, t["height"], t["width"], t["digits"])[..., None]
            for i in range(t["ring"])]
    ref_words, ref_dets = reference(cell, params, ring, fmt)
    lo_words, lo_dets = reference(cell, params, ring, lo)
    shift = fmt.frac - lo.frac
    outputs = [(i, lo_words[i % len(ring)] << shift, lo_dets[i % len(ring)])
               for i in range(n_frames)]
    return compare(outputs, n_frames, ref_words, ref_dets)
