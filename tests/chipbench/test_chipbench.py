"""Tests of the chip benchmark (`chipbench/`), on the CPU at tiny sizes.

They drive the harness's own entry points (cells by name, the runners'
set-up / window / check, the trace reduction, the counts, the control);
`chipbench/run.py` itself refuses to run without a TPU and is not run.
"""
from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import counts, gen, harness  # noqa: E402
from chipbench import reference as R  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 4099           # larger than 32 signed bits hold

# tiny traffic for each runner: frames that keep the sweep's lattice rule
# ((H - 28) % 4 == 0), a short ring, a slow open loop
TINY = {"stream": dict(height=60, width=76, ring=2),
        "classify": dict(rate=60.0, batch=8, images=16),
        "fleet": dict(replicas=1, outstanding=16, batch=8, images=16)}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(ROOT, name)
    cell.traffic.update(TINY[cell.traffic["runner"]])
    return cell


# -- every cell, configuration, mix and metric is found by name -------------

@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = harness.load_cell(ROOT, name)
    wl = {w["name"]: w for w in BENCH["workloads"]}[name]
    assert cell.config_name == wl["config"]
    assert cell.traffic_name == wl["traffic"]
    drv = harness.load_runner(ROOT, cell)
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(drv, fn))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(ROOT, m["name"]).read)


def test_manifest_files_and_names():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(names)
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["params"] == 510
        fmt, lo = cfg["format"], cfg["control_format"]
        assert lo["total_bits"] < fmt["total_bits"]
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in BENCH["per_layer"]:
        assert set(m["workloads"]) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


# -- the trace reduction, on a small recorded trace -------------------------

def _recorded_trace():
    return json.loads((ROOT / "chipbench" / "testdata"
                       / "trace_small.json").read_text())


def test_trace_reduction_by_hand():
    tr = _recorded_trace()
    red = harness.reduce(tr, chips=1)
    # the window annotation: [1000, 11000) ns
    assert red["window_s"] == pytest.approx(10_000 / 1e9)
    # ops on chip 0 inside the window: run 0 covers 1000-4500 with the
    # dense at 4000-5000 overlapping its gather, run 1 covers 6000-8000:
    # union 4000 + 2000 = 6000 ns; the op before the window is out
    assert red["busy_s"] == pytest.approx(6000 / 1e9)
    ops = red["ops"]
    assert ops[("jit_run", "_frame_trunk_jit.1")] == (pytest.approx(3e-6), 2)
    assert ops[("jit_run", "gather_fusion")] == (pytest.approx(1.5e-6), 1)
    assert ops[("jit_run", "fixed_dense")] == (pytest.approx(1e-6), 1)
    assert ("jit_run", "early_op") not in ops
    # the two program runs, their ops in the order they ran
    assert [m for m, _ in red["runs"]] == ["jit_run", "jit_run"]
    assert [n for n, _ in red["runs"][0][1]] == [
        "pad_add_fusion", "_frame_trunk_jit.1", "gather_fusion",
        "fixed_dense"]
    # idle gaps 8000-11000 (aggregate) and 5000-6000 (score), longest first
    assert red["idle_gaps"] == [["bench.aggregate", pytest.approx(3e-6)],
                                ["bench.score", pytest.approx(1e-6)]]


def test_trace_reduction_averages_chips():
    tr = _recorded_trace()
    red = harness.reduce(tr, chips=2)
    # chip 1 is busy 1000-2000 only: mean of 6000 and 1000 ns
    assert red["busy_s"] == pytest.approx(3500 / 1e9)


def test_roofline_readers_by_hand():
    cell = harness.load_cell(ROOT, "q16_sweep_1080p")
    cell.traffic.update(height=28, width=28)
    red = harness.reduce(_recorded_trace(), chips=1)
    win = harness.Window(metrics={}, attempted=2,
                         spans={"score": [0.1, 0.1], "extract": [0.0] * 2,
                                "aggregate": [0.0] * 2})
    run = harness.Run(cell=cell, window=win, trace=red,
                      peaks=counts.peaks("TPU v5 lite"))
    # trunk at 28x28: (784 + 49 + 10) words x 4 B = 3372 B at 819 GB/s,
    # over 1.5 us of kernel in each of the 2 runs
    trunk = harness.load_reader(ROOT, "trunk_roofline.sweep").read(run)
    assert trunk == pytest.approx(3372 / 819e9 / 1.5e-6 * 100)
    # head, one window: (49 + 10 + 500) x 4 = 2236 B; the ops after the
    # trunk: 2.5 us in run 0, none in run 1
    head = harness.load_reader(ROOT, "head_roofline.sweep").read(run)
    assert head == pytest.approx(2236 / 819e9 / 1.25e-6 * 100)
    # whole step: 8820 ops a frame, 2 frames in a 10 us window
    mfu = harness.load_reader(ROOT, "frame_mfu.sweep").read(run)
    assert mfu == pytest.approx(8820 * 2 / 10e-6 / 393e12 * 100)
    ms = harness.load_reader(ROOT, "head_ms.camera").read(run)
    assert ms == pytest.approx(1.25e-3)


def test_reader_finds_nothing_returns_none():
    cell = harness.load_cell(ROOT, "q16_sweep_1080p")
    win = harness.Window(metrics={}, attempted=0, spans={"score": [0.1]})
    run = harness.Run(cell=cell, window=win,
                      trace={"ops": {}, "runs": [["jit_fwd", [["x", 1.0]]]],
                             "window_s": 1.0, "busy_s": 0.0},
                      peaks=counts.peaks("TPU v5 lite"))
    for m in ("trunk_roofline.sweep", "head_roofline.sweep",
              "head_ms.camera"):
        assert harness.load_reader(ROOT, m).read(run) is None


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        counts.peaks("cpu")


# -- operation and byte counts at 28x28, by hand ----------------------------

def test_counts_28x28_by_hand():
    # conv1: 784 outputs x 4 MACs, conv2: 196 x 4, 2 ops a MAC
    assert counts.trunk(28, 28) == (2 * 4 * 784 + 2 * 4 * 196,
                                    (784 + 49 + 10) * 4)
    # dense 49 -> 10: 490 MACs
    assert counts.head(1) == (980, (49 + 10 + 500) * 4)
    # one image through the whole network: 8820 ops
    assert counts.trunk(28, 28)[0] + counts.head(1)[0] == 8820


def test_least_seconds_names_its_bound():
    peak = counts.peaks("TPU v5 lite")
    assert counts.least_seconds(393_000, 1, peak) == (pytest.approx(1e-9),
                                                      "compute")
    assert counts.least_seconds(1, 819, peak) == (pytest.approx(1e-9),
                                                  "memory")


# -- the generator is a function of the seed --------------------------------

def test_generator_repeats_from_seed():
    a, b = gen.params(SEED), gen.params(SEED)
    for k in a:
        for n in a[k]:
            np.testing.assert_array_equal(a[k][n], b[k][n])
    assert sum(v.size for layer in a.values() for v in layer.values()) == 510
    np.testing.assert_array_equal(gen.frame(SEED, 3, 60, 76, 2),
                                  gen.frame(SEED, 3, 60, 76, 2))
    np.testing.assert_array_equal(gen.arrivals(SEED, "poisson", 100, 2.0),
                                  gen.arrivals(SEED, "poisson", 100, 2.0))


@pytest.mark.parametrize("process", ["poisson", "bursty"])
def test_arrivals_fixed_count(process):
    counts_ = {len(gen.arrivals(s, process, 250.0, 4.0)) for s in (1, 2, SEED)}
    assert counts_ == {1000}
    t = gen.arrivals(SEED, process, 250.0, 4.0)
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < 4.0


# -- the reference, against the program at a tiny size ----------------------

@pytest.mark.parametrize("bits,frac", [(32, 16), (16, 8)])
def test_reference_matches_program_words(bits, frac):
    import jax.numpy as jnp
    from repro.core import backends as B
    from repro.core import fixed_point as fxp
    from repro.core import smallnet
    cfg = fxp.FixedPointConfig(bits, frac)
    p = gen.params(SEED)
    imgs = np.stack([gen.image(SEED, i) for i in range(8)])
    got = np.asarray(smallnet.apply(p, jnp.asarray(imgs),
                                    backend=B.FixedBackend(cfg=cfg)))
    np.testing.assert_array_equal(got, R.score_images(imgs, p, R.Fmt(bits,
                                                                     frac)))


# -- a run at a tiny size: correct, and due-time latencies ------------------

@pytest.mark.parametrize("name", ["q8_camera_vga30", "q8_classify_poisson"])
def test_tiny_run_is_correct(name):
    cell = tiny_cell(name)
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    try:
        w = drv.window(st, 0.5, None)
    finally:
        drv.release(st)
    ck = drv.check(st, w)
    assert w.attempted > 0
    assert ck.correct and ck.failed == 0, ck.compared


def test_due_time_latency_counts_a_stall(monkeypatch):
    from repro.streaming.fcn_sweep import FcnSweep
    cell = tiny_cell("q8_camera_vga30")
    cell.traffic["fps"] = 20
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    calls = []
    real = FcnSweep.score

    def stalled(self, *a, **k):
        calls.append(1)
        if len(calls) == 3:                   # frame 2 stalls 0.4 s
            import time
            time.sleep(0.4)
        return real(self, *a, **k)

    monkeypatch.setattr(FcnSweep, "score", stalled)
    try:
        w = drv.window(st, 1.0, None)
    finally:
        drv.release(st)
    assert drv.check(st, w).failed == 0
    # nothing is dropped; frame 2 and the frames queued behind it wait,
    # and their wait counts from their due times
    assert w.attempted == 20
    lat = sorted((i, wds) for i, wds, _ in w.outputs)
    assert len(lat) == 20
    assert w.metrics["frame_p95_ms"] >= 250.0


# -- the lower-precision control and planted faults turn `correct` false ----

@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    drv = harness.load_runner(ROOT, cell)
    ck = drv.control(cell, SEED, 12)
    assert not ck.correct
    assert ck.failed > 0


def test_fault_altered_frame_word(monkeypatch):
    from repro.streaming.fcn_sweep import FcnSweep
    cell = tiny_cell("q16_sweep_1080p")
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    real = FcnSweep.score

    def altered(self, *a, **k):
        out = np.array(real(self, *a, **k))
        out[len(out) // 2, 3] += 1            # one word of one window
        return out

    monkeypatch.setattr(FcnSweep, "score", altered)
    try:
        w = drv.window(st, 0.3, None)
    finally:
        drv.release(st)
    ck = drv.check(st, w)
    assert not ck.correct
    assert dict((n, v) for n, v, _ in ck.compared)["frames_off"] == len(
        w.outputs)


@pytest.mark.parametrize("fault", ["altered_word", "swapped_results"])
def test_fault_classify(monkeypatch, fault):
    from repro.serving import vision_engine
    real = vision_engine.smallnet.apply

    def broken(params, x, *, backend):
        out = real(params, x, backend=backend)
        if fault == "altered_word":
            return out.at[0, 0].add(1)        # the batch's first request
        return out[::-1]                      # results paired wrongly

    monkeypatch.setattr(vision_engine.smallnet, "apply", broken)
    cell = tiny_cell("q8_classify_poisson")
    cell.traffic["rate"] = 200.0              # several requests a batch
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    try:
        w = drv.window(st, 0.5, None)
    finally:
        drv.release(st)
    ck = drv.check(st, w)
    assert not ck.correct


def test_fleet_runner_tiny():
    """The router-over-replicas runner (its cell is deferred until four
    chips are free) serves a tiny closed loop correctly, and its control
    is not correct."""
    cfg = json.loads((ROOT / "chipbench/configs/smallnet_q16.json")
                     .read_text())
    traffic = json.loads((ROOT / "chipbench/traffic/fleet_closed256.json")
                         .read_text())
    traffic.update(TINY["fleet"])
    cell = harness.Cell(name="fleet", chips=1, config_name="smallnet_q16",
                        traffic_name="fleet_closed256", config=cfg,
                        traffic=traffic, end_to_end=[], per_layer=[])
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    try:
        w = drv.window(st, 0.3, None)
    finally:
        drv.release(st)
    ck = drv.check(st, w)
    assert w.attempted > traffic["outstanding"]
    assert ck.correct and ck.failed == 0, ck.compared
    assert w.metrics["images_per_s"] > 0
    for m in ("step_ms.fleet", "submit_us.fleet"):
        run = harness.Run(cell=cell, window=w, trace=None, peaks=None)
        assert harness.load_reader(ROOT, m).read(run) > 0
    assert not drv.control(cell, SEED, 12).correct


def test_result_line_shape():
    cell = harness.load_cell(ROOT, "q8_camera_vga30")
    win = harness.Window(metrics={"frame_p95_ms": 12.5, "frames_per_s": 30.0},
                         attempted=10)
    ck = harness.Check(failed=0, compared=[("frames_missing", 0, 0)])

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    out = harness.result(cell, 0, 9.5, win, ck, 123, None, [Dev()])
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "compared"
    # only the cell's own end-to-end metrics, setup_s among them
    assert set(out["metrics"]) == {"setup_s", "frame_p95_ms"}
    assert out["device"]["memory_peak_bytes"] == 123
