"""Unit tests for the observability subsystem (`repro/obs`).

Pins the contracts the serving stack and the CI trace smoke rely on:
nearest-rank percentile semantics on tiny samples, bounded-memory
instruments, the Prometheus exposition round-trip, span lifecycle and
terminal-status rules, and the flight recorder's ring/trip behavior.
"""
import json
import math

import pytest

from repro.obs import metrics as M
from repro.obs import recorder as R
from repro.obs import trace as T


@pytest.fixture(autouse=True)
def _tracing_off():
    """Never leak a process-wide tracer into other tests."""
    yield
    T.disable()


# -- percentile: nearest-rank, pinned on tiny samples -------------------------

class TestPercentile:
    def test_single_sample_every_q(self):
        for q in (0, 1, 50, 99, 100):
            assert M.percentile([10.0], q) == 10.0

    def test_four_samples_pinned(self):
        xs = [4.0, 1.0, 3.0, 2.0]          # unsorted on purpose
        # nearest-rank: k = max(1, ceil(q/100 * 4)), 1-indexed into sorted
        assert M.percentile(xs, 0) == 1.0
        assert M.percentile(xs, 25) == 1.0
        assert M.percentile(xs, 50) == 2.0
        assert M.percentile(xs, 75) == 3.0
        assert M.percentile(xs, 76) == 4.0
        assert M.percentile(xs, 99) == 4.0
        assert M.percentile(xs, 100) == 4.0

    def test_two_samples(self):
        assert M.percentile([5.0, 9.0], 50) == 5.0
        assert M.percentile([5.0, 9.0], 51) == 9.0

    def test_is_always_an_observed_value(self):
        xs = [0.1, 0.9]
        # nearest-rank never interpolates (np.percentile would give 0.5)
        assert M.percentile(xs, 50) in xs

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            M.percentile([], 50)

    def test_out_of_range_q_raises(self):
        with pytest.raises(ValueError):
            M.percentile([1.0], -1)
        with pytest.raises(ValueError):
            M.percentile([1.0], 101)


# -- instruments --------------------------------------------------------------

class TestCounter:
    def test_inc(self):
        c = M.Counter("hits", {})
        c.inc()
        c.inc(4)
        assert c.value == 5

    def test_negative_rejected(self):
        c = M.Counter("hits", {})
        with pytest.raises(ValueError):
            c.inc(-1)


class TestGauge:
    def test_set_and_hwm(self):
        g = M.Gauge("depth", {})
        g.set(3)
        g.set(7)
        g.set(2)
        assert g.value == 2
        assert g.hwm == 7
        g.reset_hwm()
        assert g.hwm == 2


class TestHistogram:
    def test_counts_and_moments_exact(self):
        h = M.Histogram("lat", {}, buckets=(1.0, 10.0))
        for v in (0.5, 2.0, 3.0, 100.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(105.5)
        assert h.min == 0.5
        assert h.max == 100.0

    def test_bucket_assignment(self):
        h = M.Histogram("lat", {}, buckets=(1.0, 10.0))
        for v in (0.5, 1.0, 2.0, 3.0, 100.0):
            h.observe(v)
        # per-bucket counts, `le` semantics, +inf last: boundary value
        # 1.0 lands in the le=1.0 bucket
        assert h.bucket_counts == [2, 2, 1]

    def test_bounded_memory(self):
        h = M.Histogram("lat", {}, buckets=(1.0,), reservoir=16)
        for i in range(1000):
            h.observe(float(i))
        assert len(h.samples()) == 16          # the bound
        assert h.count == 1000                 # exact counters unaffected
        assert h.max == 999.0

    def test_percentile_from_reservoir(self):
        h = M.Histogram("lat", {}, buckets=(1.0,))
        for v in (1.0, 2.0, 3.0, 4.0):
            h.observe(v)
        assert h.percentile(50) == 2.0

    def test_summary_ms(self):
        h = M.Histogram("lat", {}, buckets=(1.0,))
        h.observe(0.010)
        s = h.summary_ms()
        assert s["n"] == 1
        assert s["p50_ms"] == pytest.approx(10.0)
        assert M.Histogram("lat", {}, buckets=(1.0,)).summary_ms() == {"n": 0}

    def test_non_increasing_buckets_raise(self):
        with pytest.raises(ValueError):
            M.Histogram("lat", {}, buckets=(2.0, 1.0))
        with pytest.raises(ValueError):
            M.Histogram("lat", {}, buckets=(1.0, 1.0))


class TestRegistry:
    def test_get_or_create_same_instance(self):
        reg = M.Registry()
        a = reg.counter("hits", route="x")
        b = reg.counter("hits", route="x")
        assert a is b
        assert reg.counter("hits", route="y") is not a

    def test_type_mismatch_raises(self):
        reg = M.Registry()
        reg.counter("thing")
        with pytest.raises(TypeError):
            reg.gauge("thing")

    def test_prometheus_round_trip(self):
        reg = M.Registry()
        reg.counter("requests", route="a").inc(3)
        reg.gauge("depth", q="main").set(5)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(2.0)
        parsed = M.parse_prometheus(reg.to_prometheus())
        assert parsed['requests_total{route="a"}'] == 3
        assert parsed['depth{q="main"}'] == 5
        assert parsed['lat_seconds_bucket{le="0.1"}'] == 1
        assert parsed['lat_seconds_bucket{le="1.0"}'] == 2
        assert parsed['lat_seconds_bucket{le="+Inf"}'] == 3
        assert parsed["lat_seconds_count"] == 3
        assert parsed["lat_seconds_sum"] == pytest.approx(2.55)

    def test_instance_labels_unique(self):
        assert M.instance_label("eng") != M.instance_label("eng")


class TestSummarizeLatency:
    def test_values(self):
        out = M.summarize_latency([0.010, 0.020], window_s=2.0)
        assert out["latency_p50_ms"] == pytest.approx(10.0)
        assert out["latency_max_ms"] == pytest.approx(20.0)
        assert out["throughput_qps"] == pytest.approx(1.0)

    def test_zero_window(self):
        assert M.summarize_latency([0.01], window_s=0.0)[
            "throughput_qps"] == 0.0

    def test_empty_raises(self):
        with pytest.raises(ValueError):
            M.summarize_latency([], window_s=1.0)


# -- spans --------------------------------------------------------------------

class TestTracer:
    def test_start_end_lifecycle(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        root = tr.start("frame", "f-0", index=0)
        child = tr.start("tile", "f-0", parent=root)
        tr.end(child)
        tr.end(root, "served")
        assert child.parent_id == root.span_id
        assert root.status == "served" and root.terminal
        assert child.status == "ok" and not child.terminal
        assert root.t_end >= root.t_start
        assert child.t_start >= root.t_start

    def test_double_end_raises(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        s = tr.start("x", "t")
        tr.end(s)
        with pytest.raises(RuntimeError):
            tr.end(s)

    def test_span_ids_unique_and_increasing(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        a = tr.start("a", "t")
        b = tr.start("b", "t")
        assert b.span_id > a.span_id

    def test_point_is_instantaneous(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        p = tr.point("dispatch", "t", "shed:door", replica=1)
        assert 0.0 <= p.duration_s < 0.01     # two adjacent clock reads
        assert p.terminal
        assert p.tags == {"replica": 1}

    def test_context_manager_marks_errors(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        with pytest.raises(RuntimeError):
            with tr.span("work", "t"):
                raise RuntimeError("boom")
        (s,) = tr.recorder.spans()
        assert s.status == "error"

    def test_end_at_uses_given_clock(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        s = tr.start("x", "t")
        tr.end_at(s, s.t_start + 1.5, "served")
        assert s.duration_s == pytest.approx(1.5)

    def test_emit_materializes_finished_span(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        root = tr.emit("request", "t", 1.0, 3.0, "served", uid=7)
        child = tr.emit("queue_wait", "t", 1.0, 2.0, parent=root)
        assert root.terminal and root.tags == {"uid": 7}
        assert child.parent_id == root.span_id
        assert len(tr.recorder) == 2

    def test_enable_disable(self):
        assert T.get() is None
        tr = T.enable(capacity=16)
        assert T.get() is tr
        T.disable()
        assert T.get() is None

    def test_span_dict_round_trip(self):
        tr = T.Tracer(R.FlightRecorder(capacity=8))
        s = tr.emit("request", "t", 1.0, 2.0, "shed:deadline", uid=3)
        assert T.Span.from_dict(s.to_dict()) == s


# -- flight recorder ----------------------------------------------------------

class TestRegion:
    def test_records_into_ring_only_when_enabled(self):
        with T.region("engine.post") as sp:
            pass
        assert sp is None                  # tracing off: profiler only
        tr = T.enable(capacity=16)
        with T.region("sweep.score") as top:
            top.tags["n"] = 1
            with T.region("sweep.fetch", parent=top) as child:
                pass
        spans = tr.recorder.spans()
        assert [s.name for s in spans] == ["sweep.fetch", "sweep.score"]
        assert child.parent_id == top.span_id
        assert child.trace_id == top.trace_id == "sweep.score"
        assert top.tags == {"n": 1} and top.status == "ok"
        T.disable()
        with T.region("engine.post") as sp:
            pass
        assert sp is None and len(tr.recorder.spans()) == 2

    def test_marks_errors_and_reraises(self):
        tr = T.enable(capacity=16)
        with pytest.raises(ValueError):
            with T.region("engine.fill", "step-0"):
                raise ValueError("boom")
        (s,) = tr.recorder.spans()
        assert (s.trace_id, s.status) == ("step-0", "error")


class TestCompileCounter:
    def test_counts_new_shapes_not_cached_ones(self):
        import jax
        import jax.numpy as jnp

        from repro.core import runtime  # noqa: F401 — registers the listener
        compiles = M.REGISTRY.counter("jax_compiles")
        seconds = M.REGISTRY.counter("jax_compile_seconds")
        f = jax.jit(lambda x: x * 7 + 3)
        x3, x5 = jnp.ones(3), jnp.ones(5)
        f(x3).block_until_ready()
        n, t = compiles.value, seconds.value
        f(x3).block_until_ready()           # cached: no compile
        assert compiles.value == n
        f(x5).block_until_ready()           # a new shape compiles
        assert compiles.value == n + 1
        assert seconds.value > t


class TestFlightRecorder:
    def _span(self, tr, i):
        return tr.emit("frame", f"f-{i}", float(i), float(i) + 1.0, "served")

    def test_ring_is_bounded(self):
        rec = R.FlightRecorder(capacity=4)
        tr = T.Tracer(rec)
        for i in range(10):
            self._span(tr, i)
        assert len(rec) == 4
        assert rec.evicted == 6
        assert [s.trace_id for s in rec.spans()] == [
            "f-6", "f-7", "f-8", "f-9"]

    def test_dump_and_load_round_trip(self, tmp_path):
        rec = R.FlightRecorder(capacity=16)
        tr = T.Tracer(rec)
        for i in range(3):
            self._span(tr, i)
        path = rec.dump_jsonl(str(tmp_path / "t.jsonl"),
                              reason="manual", detail="x")
        header, spans = R.load_jsonl(path)
        assert header["reason"] == "manual"
        assert header["n_spans"] == 3
        assert [s.trace_id for s in spans] == ["f-0", "f-1", "f-2"]
        assert spans == rec.spans()

    def test_load_rejects_headerless_file(self, tmp_path):
        p = tmp_path / "bad.jsonl"
        p.write_text(json.dumps({"name": "frame"}) + "\n")
        with pytest.raises(ValueError):
            R.load_jsonl(str(p))

    def test_trip_rate_limited(self, tmp_path):
        rec = R.FlightRecorder(capacity=8, dump_dir=str(tmp_path),
                               trip_limit=2)
        tr = T.Tracer(rec)
        self._span(tr, 0)
        paths = [rec.trip("slo_violation", f"n{i}") for i in range(5)]
        assert sum(p is not None for p in paths) == 2
        assert rec.trip_counts() == {"slo_violation": 5}
        assert len(list(tmp_path.glob("flight_slo_violation_*.jsonl"))) == 2

    def test_dump_prometheus(self, tmp_path):
        reg = M.Registry()
        reg.counter("ticks").inc(2)
        path = R.dump_prometheus(str(tmp_path / "m.prom"), registry=reg)
        parsed = M.parse_prometheus(open(path).read())
        assert parsed["ticks_total"] == 2


# -- reconciliation -----------------------------------------------------------

def _mk(tr, name, tid, t0, t1, status="ok", parent=None):
    return tr.emit(name, tid, t0, t1, status, parent=parent)


class TestReconcile:
    def test_clean_set_reconciles(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        for i in range(3):
            root = _mk(tr, "frame", f"f-{i}", 0.0, 10.0, "served")
            _mk(tr, "tile", f"f-{i}", 1.0, 2.0, parent=root)
        _mk(tr, "frame", "f-3", 0.0, 10.0, "dropped:infer/deadline")
        fails = R.reconcile(tr.recorder.spans(),
                            frames_served=3, frames_dropped=1)
        assert fails == []

    def test_count_mismatch_detected(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        _mk(tr, "frame", "f-0", 0.0, 1.0, "served")
        fails = R.reconcile(tr.recorder.spans(),
                            frames_served=2, frames_dropped=0)
        assert any("served" in f for f in fails)

    def test_double_fate_detected(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        _mk(tr, "frame", "f-0", 0.0, 1.0, "served")
        _mk(tr, "frame", "f-0", 0.0, 1.0, "dropped:tile/queue_full")
        fails = R.reconcile(tr.recorder.spans(),
                            frames_served=1, frames_dropped=1)
        assert any("more than one root" in f for f in fails)

    def test_non_terminal_root_detected(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        _mk(tr, "frame", "f-0", 0.0, 1.0, "ok")
        fails = R.reconcile(tr.recorder.spans(), frames_served=0,
                            frames_dropped=0)
        assert any("non-terminally" in f for f in fails)

    def test_unended_root_detected(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        s = tr.start("frame", "f-0")
        rec_spans = [s]
        fails = R.reconcile(rec_spans, frames_served=0, frames_dropped=0)
        assert any("never ended" in f for f in fails)

    def test_child_escaping_parent_detected(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        root = _mk(tr, "frame", "f-0", 0.0, 1.0, "served")
        _mk(tr, "tile", "f-0", 0.5, 2.0, parent=root)   # ends after parent
        fails = R.reconcile(tr.recorder.spans(),
                            frames_served=1, frames_dropped=0)
        assert any("escapes" in f for f in fails)

    def test_backwards_clock_detected(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        _mk(tr, "frame", "f-0", 5.0, 1.0, "served")
        fails = R.reconcile(tr.recorder.spans(),
                            frames_served=1, frames_dropped=0)
        assert any("backwards" in f for f in fails)

    def test_nested_request_roots_share_trace_id(self):
        # request spans under a frame legitimately share the frame's
        # trace_id — uniqueness applies only to true roots (no parent)
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        frame = _mk(tr, "frame", "f-0", 0.0, 10.0, "served")
        for _ in range(3):
            _mk(tr, "request", "f-0", 1.0, 2.0, "served", parent=frame)
        fails = R.reconcile(tr.recorder.spans(), served=3, shed=0,
                            root_name="request")
        assert fails == []


class TestWaterfall:
    def test_renders_all_spans(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        root = _mk(tr, "frame", "f-0", 0.0, 10.0, "served")
        _mk(tr, "tile", "f-0", 1.0, 2.0, parent=root)
        out = R.waterfall(tr.recorder.spans(), "f-0")
        assert "frame" in out and "tile" in out and "served" in out

    def test_max_spans_truncates_explicitly(self):
        tr = T.Tracer(R.FlightRecorder(capacity=64))
        root = _mk(tr, "frame", "f-0", 0.0, 10.0, "served")
        for i in range(10):
            _mk(tr, "request", "f-0", 1.0, 2.0, "served", parent=root)
        out = R.waterfall(tr.recorder.spans(), "f-0", max_spans=4)
        assert "+7 more spans" in out

    def test_unknown_trace(self):
        assert "no spans" in R.waterfall([], "nope")


def test_latency_buckets_are_strictly_increasing():
    bs = M.LATENCY_BUCKETS_S
    assert all(a < b for a, b in zip(bs, bs[1:]))
    assert not math.isinf(bs[-1])
