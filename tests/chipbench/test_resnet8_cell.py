"""Tests of the benchmark's ResNet-8 cell and the QQVGA sweep cell, on the
CPU at tiny traffic: both cells load by name, the ResNet-8 runner serves
word-exact results through `VisionEngine` and its control is not correct,
the plain reference agrees with the program, and the three ResNet-8
readers read a recorded trace.
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

from chipbench import counts, counts_resnet8, gen_resnet8, harness  # noqa: E402
from chipbench import reference_resnet8 as R8  # noqa: E402
from chipbench.reference import Fmt  # noqa: E402

SEED = 2**31 + 4099
RESNET8_CELL = "q16_resnet8_classify_poisson"
QQVGA_CELL = "q16_sweep_qqvga"
READERS = ("conv_ms.resnet8", "conv_roofline.resnet8", "image_mfu.resnet8")


def tiny_resnet8_cell() -> harness.Cell:
    cell = harness.load_cell(ROOT, RESNET8_CELL)
    cell.traffic.update(rate=40.0, batch=8, images=12)
    return cell


# -- the cells and the manifest ---------------------------------------------

@pytest.mark.parametrize("name", [RESNET8_CELL, QQVGA_CELL])
def test_new_cell_loads(name):
    cell = harness.load_cell(ROOT, name)
    drv = harness.load_runner(ROOT, cell)
    for fn in ("setup", "window", "release", "check", "control"):
        assert callable(getattr(drv, fn))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) == 2
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_reader(ROOT, m["name"]).read)


def test_manifest_configs_by_model():
    """Every configuration file states its model's parameter count and a
    control format narrower than its own; names are unique and every
    metric's cells exist."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    params = {"smallnet": 510, "resnet8": 77706}
    cells = {w["name"] for w in bench["workloads"]}
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    assert {w["config"] for w in bench["workloads"]} == set(names)
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["params"] == params[cfg["model"]]
        assert (cfg["control_format"]["total_bits"]
                < cfg["format"]["total_bits"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells


def test_resnet8_cell_reports_its_metrics():
    cell = harness.load_cell(ROOT, RESNET8_CELL)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                   "image_p95_ms"}
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    assert cell.config["params"] == 77706
    assert cell.config["format"]["total_bits"] == 32
    assert cell.config["control_format"]["total_bits"] == 16
    assert cell.traffic["batch"] == 32 and cell.traffic["images"] == 1024


def test_qqvga_cell_is_data_on_the_stream_runner():
    cell = harness.load_cell(ROOT, QQVGA_CELL)
    t = cell.traffic
    assert t["runner"] == "stream" and t["fps"] is None
    assert (t["height"], t["width"], t["stride"]) == (120, 160, 8)
    # the sweep's lattice rule and the megakernel's geometry hold
    assert (t["height"] - 28) % 4 == 0 and (t["width"] - 28) % 4 == 0
    assert t["height"] % 4 == 0 and t["width"] % 4 == 0
    assert {m["name"] for m in cell.end_to_end} == {"setup_s",
                                                   "frames_per_s"}
    assert {m["name"] for m in cell.per_layer} == {"score_ms.sweep",
                                                   "host_ms.sweep"}


# -- counts, by hand --------------------------------------------------------

def test_counts_by_hand():
    assert counts_resnet8.macs_per_image() == 12501632
    # stem: 32x32 outputs x 16 channels x (2 x 27 MAC ops + 1 bias add);
    # reads 32x32x3 words, writes 32x32x16, reads 432 + 16 parameters
    assert counts_resnet8.conv_layer("stem", 1) == (
        1024 * 16 * 55, (3072 + 16384 + 448) * 4)
    ops, nbytes = counts_resnet8.convs(2)
    assert ops == 2 * counts_resnet8.convs(1)[0]
    conv_ops = 2 * (12501632 - 640) + sum(
        (-(-side // s)) ** 2 * cout
        for side, _, _, cout, s in counts_resnet8.CONVS.values())
    assert counts_resnet8.convs(1)[0] == conv_ops
    # + 3 residual adds, the pool's 4096 adds, the dense 64 -> 10 + bias
    assert counts_resnet8.model_ops_per_image() == (
        conv_ops + 16384 + 8192 + 4096 + 4096 + 1290)


# -- the reference and the control ------------------------------------------

def test_reference_repeats_and_control_differs():
    p = gen_resnet8.params(SEED)
    imgs = gen_resnet8.images(SEED, 3)
    a = R8.score_images(imgs, p, Fmt(32, 16), block=1)
    b = R8.score_images(imgs, p, Fmt(32, 16), block=2, workers=1)
    np.testing.assert_array_equal(a, b)
    low = R8.score_images(imgs, p, Fmt(16, 8)) << 8
    assert np.all((low != a).any(axis=1))      # every request is off


def test_generator_repeats_from_seed():
    a, b = gen_resnet8.params(SEED), gen_resnet8.params(SEED)
    for k in a:
        for n in a[k]:
            np.testing.assert_array_equal(a[k][n], b[k][n])
    assert sum(v.size for layer in a.values() for v in layer.values()) \
        == 77706
    x = gen_resnet8.images(SEED, 4)
    np.testing.assert_array_equal(x, gen_resnet8.images(SEED, 4))
    assert x.shape == (4, 32, 32, 3) and x.min() >= 0 and x.max() <= 1


def test_control_is_not_correct_on_every_request():
    cell = tiny_resnet8_cell()
    drv = harness.load_runner(ROOT, cell)
    ck = drv.control(cell, SEED, 12)
    compared = {n: v for n, v, _ in ck.compared}
    assert not ck.correct
    assert compared["requests_off"] == 12
    assert compared["requests_missing"] == 0


# -- a run at a tiny traffic, through VisionEngine on fixed_pallas ----------

def test_tiny_resnet8_run_is_correct(capsys):
    cell = tiny_resnet8_cell()
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    try:
        w = drv.window(st, 0.5, None)
    finally:
        drv.release(st)
    ck = drv.check(st, w)
    assert w.attempted == 20
    assert ck.correct and ck.failed == 0, ck.compared
    assert "image_p95_ms" in w.metrics
    assert "activation_peak_words stem=" in capsys.readouterr().out


def test_altered_word_is_caught(monkeypatch):
    from repro.core import resnet8
    real = resnet8.apply

    def altered(params, x, *, backend):
        return real(params, x, backend=backend).at[0, 0].add(1)

    monkeypatch.setattr(resnet8, "apply", altered)
    cell = tiny_resnet8_cell()
    drv = harness.load_runner(ROOT, cell)
    st = drv.setup(cell, SEED, None)
    try:
        w = drv.window(st, 0.5, None)
    finally:
        drv.release(st)
    ck = drv.check(st, w)
    assert not ck.correct
    assert dict((n, v) for n, v, _ in ck.compared)["score_words_off"] > 0


# -- the readers, on a recorded trace ----------------------------------------

def _recorded_run(served: int) -> harness.Run:
    tr = json.loads((ROOT / "chipbench" / "testdata"
                     / "trace_resnet8.json").read_text())
    red = harness.reduce(tr, chips=1)
    win = harness.Window(metrics={}, attempted=served,
                         outputs=[None] * served)
    return harness.Run(cell=harness.load_cell(ROOT, RESNET8_CELL),
                       window=win, trace=red,
                       peaks=counts.peaks("TPU v5 lite"))


def test_readers_by_hand_on_recorded_trace():
    run = _recorded_run(served=48)
    red = run.trace
    steps = [ops for module, ops in red["runs"] if module == "jit_fwd"]
    conv = [sum(t for n, t in ops if n.startswith("_fixed_conv_mc_jit"))
            for ops in steps]
    assert len(steps) == 3 and all(c > 0 for c in conv)
    assert harness.load_reader(ROOT, "conv_ms.resnet8").read(run) \
        == pytest.approx(sum(conv) / 3 * 1e3)
    least = max(counts_resnet8.convs(48)[0] / 393e12,
                counts_resnet8.convs(48)[1] / 819e9)
    roof = harness.load_reader(ROOT, "conv_roofline.resnet8").read(run)
    assert roof == pytest.approx(least / sum(conv) * 100)
    assert 0 < roof <= 100
    mfu = harness.load_reader(ROOT, "image_mfu.resnet8").read(run)
    assert mfu == pytest.approx(counts_resnet8.model_ops_per_image() * 48
                                / red["window_s"] / 393e12 * 100)


def test_readers_read_nothing_without_the_kernel():
    """A program without the conv kernel (the parent's) gives no reading,
    and the readers do not raise."""
    run = _recorded_run(served=48)
    run.trace = {"ops": {("jit_fwd", "_fixed_conv2d_jit.2"): (0.01, 4)},
                 "runs": [["jit_fwd", [["_fixed_conv2d_jit.2", 0.01]]]],
                 "window_s": 10.0, "busy_s": 0.01}
    for m in READERS:
        assert harness.load_reader(ROOT, m).read(run) is None
