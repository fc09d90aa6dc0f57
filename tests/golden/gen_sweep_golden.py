"""Generator for tests/golden/sweep_golden.json — run once, commit the JSON.

    PYTHONPATH=src python tests/golden/gen_sweep_golden.py

Freezes the Q16.16 words of the FCN sweep trunk over one deterministic
112x112 frame with smallNet's seeded params: all four pooled role maps
(interior / last_row / last_col / corner, 28x28 int32 each) plus the
(144, 10) window-score words of the stride-8 sweep.

The file stores its INPUTS next to its outputs — the Q16.16 parameter
words and the Q16.16 frame words — and the generator and tests read them
from there, so the frozen vectors pin the arithmetic and nothing else (not
the `jax.random` stream behind `smallnet.seeded_params`).  The inputs were
drawn once: the "fixed" backend's quantization of `smallnet.seeded_params()`
(JAX 0.9.0) and `fixed_point.to_fixed` of frame 0 of
`SyntheticVideoSource(seed=7)`.  Generation cross-checks three substrates
and fails loudly on any disagreement:

  * the emulated "fixed" sweep vs the "fixed_pallas" kernel sweep
    (word-for-word on every map and score), and
  * the sweep scores vs the host Tiler's patch-extract-and-score path on
    the same window lattice — the independent patch-wise semantics that
    the quad cascade must reproduce.

So the frozen vectors pin the sweep's padding/edge arithmetic itself, not
just one implementation of it.  The CI golden job regenerates this file
and diffs it, exactly like fixed_golden.json.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from repro.core import fixed_point as fxp
from repro.core import smallnet
from repro.streaming.fcn_sweep import FcnSweep, composed_feature_maps
from repro.streaming.tiler import Tiler

STRIDE = 8
MAPS = ("interior", "last_row", "last_col", "corner")
PATH = pathlib.Path(__file__).parent / "sweep_golden.json"


def _check_equal(name, a, b):
    if not np.array_equal(np.asarray(a, np.int64), np.asarray(b, np.int64)):
        raise SystemExit(f"substrate drift while generating {name!r}")
    return np.asarray(a, np.int64)


def golden_inputs(path: pathlib.Path = PATH) -> dict:
    """The stored {"params", "frame"} Q16.16 input words of a golden
    file."""
    return json.loads(path.read_text())["inputs"]


def decode_inputs(inputs: dict) -> tuple[dict, np.ndarray]:
    """Stored input words -> (float params, (H, W, 1) float32 frame) that
    quantize back to exactly those words."""
    frame = np.asarray(fxp.from_fixed(np.asarray(inputs["frame"], np.int32)))
    return smallnet.params_from_words(inputs["params"]), frame[..., None]


def main() -> None:
    inputs = golden_inputs()
    params, pixels = decode_inputs(inputs)

    maps = {}
    # the maps pin the COMPOSED per-stage decomposition itself — the
    # one-launch frame_trunk route has its own frozen vectors
    # (frame_trunk_golden.json), so each route is pinned independently
    by_backend = {b: composed_feature_maps(params, pixels, backend=b)
                  for b in ("fixed", "fixed_pallas")}
    for name in MAPS:
        maps[name] = _check_equal(f"map/{name}",
                                  by_backend["fixed"][name],
                                  by_backend["fixed_pallas"][name]).tolist()

    sweep = FcnSweep(stride=STRIDE)
    fb, pos = sweep.extract(pixels)
    scores = _check_equal("scores",
                          sweep.score(params, fb, backend="fixed"),
                          sweep.score(params, fb, backend="fixed_pallas"))
    tiler = Tiler(stride=STRIDE)
    tiles, pos_t = tiler.extract(pixels)
    assert pos == pos_t
    patch_scores = tiler.score(params, tiles, backend="fixed")
    _check_equal("scores vs host tiler", scores, patch_scores)

    out = {
        "frame": {"source": "SyntheticVideoSource(n_frames=1, seed=7)",
                  "index": 0, "shape": list(pixels.shape[:2])},
        "format": "q16_16", "stride": STRIDE,
        "inputs": inputs,
        "positions": [list(p) for p in pos],
        "maps": maps,
        "scores": scores.tolist(),
    }
    PATH.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {PATH} ({PATH.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
