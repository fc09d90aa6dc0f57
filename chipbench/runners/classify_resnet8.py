"""Classify runner for ResNet-8: single 32x32x3 images through
`VisionEngine(model=resnet8)`.

Requests go through a continuously batched `VisionEngine` serving
`core.resnet8.apply` on the `fixed_pallas` backend in the configuration's
word format (the `fixed_conv_mc` conv kernel, `fixed_dense`), with no
admission bound, no deadline and no service-time floor, so `failed` counts
only requests that are missing or whose words differ from the reference
(`chipbench/reference_resnet8.py`).

Traffic parameters (`chipbench/traffic/<mix>.json`): as the classify
runner's (process, rate, batch, images); the images are 32x32x3 crops
and the weights BN-folded ResNet-8 parameters, both drawn from the seed
(`chipbench/gen_resnet8.py`). The measured window, the release and the
comparison are the classify runner's own.
"""
from __future__ import annotations

import numpy as np

from chipbench import gen_resnet8, harness
from chipbench import reference_resnet8 as R8
from chipbench.reference import Fmt
from chipbench.runners.classify import State, compare, release, window
from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import resnet8
from repro.serving.vision_engine import VisionEngine

__all__ = ["setup", "window", "release", "check", "control"]


def setup(cell: harness.Cell, seed: int, devices) -> State:
    t, c = cell.traffic, cell.config
    cfg = fxp.FixedPointConfig(**c["format"])
    params = gen_resnet8.params(seed)
    images = gen_resnet8.images(seed, t["images"])
    engine = VisionEngine(params, backend=B.FixedPallasBackend(cfg=cfg),
                          model=resnet8, batch_size=t["batch"],
                          max_queue=None, min_step_s=0.0, warmup=True)
    engine.start()
    # warm-up through the serving thread: full and partial batches
    uids = [engine.submit(images[i % len(images)])
            for i in range(4 * t["batch"] + 3)]
    engine.wait(uids, timeout=300.0)
    engine.pop_results(uids)
    return State(cell, seed, params, images, engine)


def _used(images: np.ndarray, n_requests: int) -> np.ndarray:
    """The images request k % len(images) carried, for k < n_requests."""
    return images[:min(len(images), n_requests)]


def check(st: State, w: harness.Window) -> harness.Check:
    peaks: dict = {}
    ref = R8.score_images(_used(st.images, w.attempted), st.params,
                          Fmt.of(st.cell.config["format"]), peaks=peaks)
    print("activation_peak_words " + " ".join(
        f"{k}={v}" for k, v in peaks.items()), flush=True)
    return compare(w.outputs, w.attempted, ref)


def control(cell: harness.Cell, seed: int, n_requests: int) -> harness.Check:
    """The reference in `control_format` put where the engine's results go,
    words carried into the configuration's units, compared as a run's
    are."""
    fmt = Fmt.of(cell.config["format"])
    lo = Fmt.of(cell.config["control_format"])
    images = _used(gen_resnet8.images(seed, cell.traffic["images"]),
                   n_requests)
    params = gen_resnet8.params(seed)
    ref = R8.score_images(images, params, fmt)
    low = R8.score_images(images, params, lo) << (fmt.frac - lo.frac)
    outputs = [(k, low[k % len(low)], int(np.argmax(low[k % len(low)])))
               for k in range(n_requests)]
    return compare(outputs, n_requests, ref)
