"""Tests of the host-span reduction (`chipbench/host_spans.py`) and of the
program's profiler regions it reads, on the CPU.

The reduction is checked by hand on a small recorded trace with program
spans nested on two thread lines; the regions are checked by running a
tiny sweep pipeline and an engine under a live `jax.profiler` trace.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import harness  # noqa: E402
from chipbench import host_spans as H  # noqa: E402

SEED = 2**31 + 4099


def _fixture(name):
    return json.loads((ROOT / "chipbench" / "testdata" / name).read_text())


# -- the reduction, by hand -------------------------------------------------

def test_host_spans_by_hand():
    got = H.host_spans(_fixture("trace_spans.json"))
    want = {
        # window [1000, 21000): the first score starts before it, so its
        # spans are clipped at 1000; self time is what the four nested
        # sweep spans leave (50 ns in each call)
        "sweep.score": [10_100, 2, 100],
        "sweep.prepare": [600, 2, 600],
        "sweep.upload": [400, 2, 400],
        "sweep.dispatch": [600, 2, 600],
        "sweep.fetch": [8_400, 2, 8_400],
        # the second aggregate is clipped at 21000, its select with it;
        # bench.aggregate between them is not a program span, so the
        # confidences and select count against pipeline.aggregate
        "pipeline.aggregate": [10_000, 2, 200],
        "pipeline.confidences": [6_900, 2, 6_900],
        "pipeline.select": [2_900, 2, 2_900],
        # the second tile and the engine.post lie outside the window
        "pipeline.tile": [100, 1, 100],
    }
    assert set(got) == set(want)
    for name, (tot, n, own) in want.items():
        assert got[name] == [pytest.approx(tot / 1e9), n,
                             pytest.approx(own / 1e9)], name


def test_gap_named_after_innermost_program_span():
    # idle [6000, 12000): pipeline.aggregate, bench.aggregate and
    # pipeline.confidences each cover at least half; the confidences are
    # the innermost. Idle [16000, 21000) likewise; idle [1000, 2000):
    # bench.score, sweep.score and sweep.prepare cover half, upload less
    assert H.idle_gaps(_fixture("trace_spans.json")) == [
        ["pipeline.confidences", pytest.approx(6e-6)],
        ["pipeline.confidences", pytest.approx(5e-6)],
        ["sweep.prepare", pytest.approx(1e-6)]]


def test_gap_naming_falls_back():
    spans = [("engine.post", 0, 300, 1), ("engine.wait", 600, 1000, 1)]
    # nothing covers half of [0, 1000): the span covering most names it
    assert H.name_gap(spans, 0, 1000) == "engine.wait"
    assert H.name_gap(spans, 300, 500) == "host.other"


def test_bench_only_trace_names_gaps_as_the_harness_does():
    tr = _fixture("trace_small.json")
    assert H.idle_gaps(tr) == harness.reduce(tr, chips=1)["idle_gaps"]


def test_split_lists_long_gaps_and_long_bench_spans():
    from chipbench import trace_split
    tr = _fixture("trace_spans.json")
    k = 20_000                             # 1 ns of the fixture -> 20 us
    tr = {"devices": {p: [o[:3] + [o[3] * k, o[4] * k] for o in ops]
                      for p, ops in tr["devices"].items()},
          "host": [[h[0], h[1] * k, h[2] * k, h[3]] for h in tr["host"]]}
    got = trace_split.split(tr)
    assert got["window_s"] == pytest.approx(0.4)
    assert got["busy_s"] == pytest.approx(0.16)
    assert got["host_spans"] == H.host_spans(tr)
    assert got["idle_gaps"] == H.idle_gaps(tr)

    def approx(rows):
        return [[n, pytest.approx(v)] for n, v in rows]

    # idle [6000, 12000) and [16000, 21000) exceed 50 ms; each lists the
    # four spans that overlap it most
    assert got["gaps_over_50ms"] == [
        [pytest.approx(0.12), approx([
            ["pipeline.aggregate", 0.11], ["bench.aggregate", 0.108],
            ["pipeline.confidences", 0.068], ["pipeline.select", 0.04]])],
        [pytest.approx(0.1), approx([
            ["pipeline.aggregate", 0.08], ["bench.aggregate", 0.08],
            ["pipeline.confidences", 0.07], ["pipeline.select", 0.01]])]]
    # the first score (clipped to 110 ms) and the first aggregate (116
    # ms) exceed 100 ms; each lists the program spans on its own line
    assert got["bench_over_100ms"] == [
        ["bench.score", pytest.approx(0.11), approx([
            ["sweep.score", 0.109], ["sweep.fetch", 0.084],
            ["sweep.prepare", 0.01], ["sweep.dispatch", 0.008]])],
        ["bench.aggregate", pytest.approx(0.116), approx([
            ["pipeline.aggregate", 0.116], ["pipeline.confidences", 0.068],
            ["pipeline.select", 0.048]])]]


# -- the queue-wait reader --------------------------------------------------

def test_queue_wait_reader(monkeypatch):
    from repro.obs import metrics as M
    cell = harness.load_cell(ROOT, "q8_camera_vga30")
    cell.traffic.update(height=60, width=76, ring=2, fps=20)
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    try:
        w = drv.window(st, 0.5, None)
    finally:
        drv.release(st)
    run = harness.Run(cell=cell, window=w, trace=None, peaks=None)
    reader = harness.load_reader(ROOT, "queue_wait_ms.camera")
    v = reader.read(run)
    assert v is not None and 0.0 < v < 500.0
    # a program without the histograms (or no pipeline at all) reads None
    n = len(w.outputs)
    reg = M.Registry()
    monkeypatch.setattr(M, "REGISTRY", reg)
    assert reader.read(run) is None
    reg.counter("stream_frames_served", pipe="pipe#3").inc(n)
    assert reader.read(run) is None
    reg.histogram("stream_queue_wait_seconds", stage="tile",
                  pipe="pipe#3").observe(0.002 * n)
    reg.histogram("stream_executor_hop_seconds", pipe="pipe#3").observe(
        0.002 * n)
    reg.histogram("stream_queue_wait_seconds", stage="tile",
                  pipe="pipe#2").observe(5.0)
    assert reader.read(run) == pytest.approx(4.0)
    # a pipeline built after the window's: None, not its numbers
    reg.counter("stream_frames_served", pipe="pipe#4").inc(2)
    reg.histogram("stream_executor_hop_seconds", pipe="pipe#4").observe(1.0)
    assert reader.read(run) is None


# -- the program's regions, under a live profiler ---------------------------

SWEEP_SPANS = {"pipeline.tile", "pipeline.aggregate", "pipeline.confidences",
               "pipeline.select", "sweep.score", "sweep.prepare",
               "sweep.upload", "sweep.dispatch", "sweep.fetch"}
ENGINE_SPANS = {"engine.batch_form", "engine.fill", "engine.device_step",
                "engine.post", "engine.wait"}


def _traced(tmp_path, body):
    with harness.profiled(str(tmp_path)):
        body()
    return H.load(str(tmp_path))


def test_program_regions_reach_the_profiler(tmp_path):
    from repro.core import smallnet
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming.fcn_sweep import FcnSweep
    from repro.streaming.pipeline import StreamConfig, StreamingPipeline
    from repro.streaming.sources import Frame

    params = smallnet.seeded_params()
    eng = VisionEngine(params, backend="ref", batch_size=4)
    rng = np.random.default_rng(0)
    frames = [Frame(index=i, pixels=rng.random((56, 56, 1), np.float32),
                    truth=[], t_source=0.0) for i in range(2)]

    def pipeline():
        StreamingPipeline(frames, eng, FcnSweep(stride=8),
                          config=StreamConfig()).run()

    StreamingPipeline(frames, eng, FcnSweep(stride=8)).run()   # compile
    got = H.host_spans(_traced(tmp_path / "sweep", pipeline))
    assert SWEEP_SPANS <= set(got)
    for name in SWEEP_SPANS:
        assert got[name][1] == 2, name         # one per frame
    tot, _, own = got["sweep.score"]
    assert 0.0 <= own < tot

    eng.start()
    try:
        uid = eng.submit(frames[0].pixels[:28, :28])
        eng.wait([uid], timeout=60.0)          # warm the serving thread

        def steps():
            # two requests apart: the serving thread waits between them
            for i in range(2):
                eng.wait([eng.submit(frames[i].pixels[:28, :28])],
                         timeout=60.0)
                time.sleep(0.05)

        got = H.host_spans(_traced(tmp_path / "engine", steps))
    finally:
        eng.stop()
    assert ENGINE_SPANS <= set(got)
    assert got["engine.device_step"][1] == 2
