"""Observability integration: spans vs. the real serving-stack ledgers.

Runs the actual pipeline/engine with tracing enabled and asserts the
contract the CI trace smoke gates on: every submitted frame (and every
engine request) ends in exactly one terminal span state that reconciles
with the component's own accounting, span clocks are monotonic and
nested, the flight recorder trips on SLO violations, and the registry's
Prometheus exposition round-trips after a live run.
"""
import numpy as np
import pytest

from repro.core import smallnet
from repro.obs import metrics as M
from repro.obs import recorder as R
from repro.obs import trace as T
from repro.serving.vision_engine import VisionEngine
from repro.streaming.pipeline import StreamConfig, StreamingPipeline
from repro.streaming.sources import PacedPlayer, SyntheticVideoSource
from repro.streaming.tiler import Tiler


@pytest.fixture(scope="module")
def params():
    return smallnet.seeded_params()


@pytest.fixture(scope="module")
def clip():
    return SyntheticVideoSource(n_frames=6, seed=3)


@pytest.fixture(scope="module")
def tiler(params, clip):
    t0 = Tiler(stride=14)
    tiles, _ = t0.extract(clip.frames()[0])
    conf = t0._confidences(t0.score(params, tiles, backend="ref")).max(-1)
    return Tiler(stride=14, threshold=float(np.quantile(conf, 0.8)))


@pytest.fixture()
def tracer(tmp_path):
    tr = T.enable(capacity=1 << 15, dump_dir=str(tmp_path))
    yield tr
    T.disable()


def _run_pipeline(params, clip, tiler, **cfg):
    engine = VisionEngine(params, backend="ref", batch_size=64)
    pipe = StreamingPipeline(clip, engine, tiler,
                             config=StreamConfig(**cfg))
    pipe.run()
    return pipe


# -- the headline contract: spans reconcile with both ledgers -----------------

class TestTracedPipelineReconciles:
    def test_frame_and_request_ledgers(self, params, clip, tiler, tracer):
        pipe = _run_pipeline(params, clip, tiler)
        s = pipe.stats()
        spans = tracer.recorder.spans()
        assert tracer.recorder.evicted == 0

        # every submitted frame ends in exactly one terminal frame span
        # matching the pipeline ledger
        assert R.reconcile(spans, frames_served=s["frames_served"],
                           frames_dropped=s["frames_dropped"]) == []
        # and every engine request reconciles against the engine ledger
        es = s["engine"]
        assert es["accounted"]
        assert R.reconcile(spans, served=es["n"], shed=es["shed"],
                           root_name="request") == []

    def test_span_taxonomy_present(self, params, clip, tiler, tracer):
        _run_pipeline(params, clip, tiler)
        names = {sp.name for sp in tracer.recorder.spans()}
        for expected in ("frame", "pipeline.tile", "infer",
                         "pipeline.aggregate", "request", "queue_wait",
                         "engine.batch_form", "engine.fill",
                         "engine.device_step", "engine.post"):
            assert expected in names, f"missing {expected!r} spans"

    def test_one_frame_root_per_ingested_frame(self, params, clip, tiler,
                                               tracer):
        pipe = _run_pipeline(params, clip, tiler)
        roots = [sp for sp in tracer.recorder.spans()
                 if sp.name == "frame" and sp.parent_id is None]
        assert len(roots) == pipe.stats()["frames_in"]
        assert all(r.terminal for r in roots)

    def test_stage_spans_nest_inside_their_frame(self, params, clip, tiler,
                                                 tracer):
        _run_pipeline(params, clip, tiler)
        spans = tracer.recorder.spans()
        by_id = {sp.span_id: sp for sp in spans}
        checked = 0
        for sp in spans:
            if sp.name not in ("pipeline.tile", "infer",
                               "pipeline.aggregate"):
                continue
            parent = by_id[sp.parent_id]
            assert parent.name == "frame"
            assert sp.t_start >= parent.t_start - 1e-6
            assert sp.t_end <= parent.t_end + 1e-6
            checked += 1
        assert checked > 0


class TestDroppedFrames:
    def test_deadline_drops_reconcile_and_trip(self, params, clip, tiler,
                                               tracer, tmp_path):
        # an impossible deadline: every frame is dropped, none served
        pipe = _run_pipeline(params, clip, tiler, deadline_ms=1e-3)
        s = pipe.stats()
        assert s["frames_served"] == 0
        assert s["frames_dropped"] == s["frames_in"] > 0
        spans = tracer.recorder.spans()
        assert R.reconcile(spans, frames_served=0,
                           frames_dropped=s["frames_dropped"]) == []
        roots = [sp for sp in spans
                 if sp.name == "frame" and sp.parent_id is None]
        assert all(r.status.startswith("dropped:") for r in roots)
        # deadline misses tripped the flight recorder (rate-limited)
        assert tracer.recorder.trip_counts().get("slo_violation", 0) > 0
        dumped = list(tmp_path.glob("flight_slo_violation_*.jsonl"))
        assert 1 <= len(dumped) <= tracer.recorder.trip_limit
        header, dumped_spans = R.load_jsonl(str(dumped[0]))
        assert header["reason"] == "slo_violation"
        assert len(dumped_spans) == header["n_spans"]


class TestTracedEngineStandalone:
    def test_door_sheds_and_serves_reconcile(self, params, tracer):
        engine = VisionEngine(params, backend="ref", batch_size=4,
                              max_queue=3)
        rng = np.random.default_rng(0)
        imgs = rng.random((8, 28, 28, 1), dtype=np.float32)
        for img in imgs:
            engine.submit(img)           # queue bound 3: 5 shed at the door
        engine.run()
        es = engine.stats()
        assert es["submitted"] == 8
        assert es["n"] == 3 and es["shed"] == 5
        assert es["accounted"]
        spans = tracer.recorder.spans()
        assert R.reconcile(spans, served=es["n"], shed=es["shed"],
                           root_name="request") == []
        sheds = [sp for sp in spans if sp.name == "request"
                 and sp.status == "shed:queue_depth"]
        assert len(sheds) == 5
        # served requests carry a queue_wait child inside their window
        served = [sp for sp in spans if sp.name == "request"
                  and sp.status == "served"]
        qw_parents = {sp.parent_id for sp in spans
                      if sp.name == "queue_wait"}
        assert {sp.span_id for sp in served} <= qw_parents


# -- satellite 1: bounded memory in the pipeline's stage timings --------------

class TestBoundedRetention:
    def test_stage_histograms_are_bounded(self, params, clip, tiler):
        pipe = _run_pipeline(params, clip, tiler)
        for hist in list(pipe._stage_hist.values()) + [pipe._lat_hist]:
            assert hist._samples.maxlen == M.RESERVOIR
            assert len(hist._samples) <= hist._samples.maxlen
            # exact accumulators live outside the reservoir
            assert hist.count >= len(hist._samples)

    def test_retention_is_constant_past_the_reservoir(self):
        h = M.Histogram("stage", {}, buckets=(0.01,), reservoir=32)
        for i in range(10 * 32):
            h.observe(i * 1e-4)
        assert len(h.samples()) == 32
        assert h.count == 320
        # summary still reports the exact stream count, not the window
        assert h.summary_ms()["n"] == 320

    def test_no_unbounded_stat_lists_on_pipeline(self, params, clip, tiler):
        # the pre-registry ad-hoc lists must not come back
        pipe = _run_pipeline(params, clip, tiler)
        for attr in ("_stage_s", "_latencies", "_lat_s"):
            assert not hasattr(pipe, attr)


# -- queue waits and the executor hop -----------------------------------------

class TestQueueWaits:
    def test_one_wait_per_served_frame_per_stage(self, params, clip, tiler):
        pipe = _run_pipeline(params, clip, tiler)
        served = pipe.stats()["frames_served"]
        assert served == len(clip.frames())
        for stage in ("tile", "infer", "aggregate"):
            h = M.REGISTRY.histogram("stream_queue_wait_seconds",
                                     stage=stage, pipe=pipe._id)
            assert h.count == served and h.min >= 0.0
        hop = M.REGISTRY.histogram("stream_executor_hop_seconds",
                                   pipe=pipe._id)
        assert hop.count == served and hop.min >= 0.0


# -- live-registry export after a real run ------------------------------------

class TestLiveRegistryExport:
    def test_prometheus_round_trips_after_run(self, params, clip, tiler):
        pipe = _run_pipeline(params, clip, tiler)
        s = pipe.stats()
        parsed = M.parse_prometheus(M.REGISTRY.to_prometheus())
        pid = pipe._id
        assert parsed[f'stream_frames_in_total{{pipe="{pid}"}}'] == \
            s["frames_in"]
        assert parsed[f'stream_frames_served_total{{pipe="{pid}"}}'] == \
            s["frames_served"]
        key = f'stream_frame_latency_seconds_count{{pipe="{pid}"}}'
        assert parsed[key] == s["frames_served"]

    def test_realtime_pipeline_reconciles_too(self, params, clip, tiler,
                                              tracer):
        engine = VisionEngine(params, backend="ref", batch_size=64)
        pipe = StreamingPipeline(
            PacedPlayer(clip, fps=30.0), engine, tiler,
            config=StreamConfig(deadline_ms=2000.0, queue_size=4))
        pipe.run()
        s = pipe.stats()
        assert s["accounted"]
        assert R.reconcile(tracer.recorder.spans(),
                           frames_served=s["frames_served"],
                           frames_dropped=s["frames_dropped"]) == []
