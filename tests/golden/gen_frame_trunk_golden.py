"""Generator for tests/golden/frame_trunk_golden.json — run once, commit.

    PYTHONPATH=src python tests/golden/gen_frame_trunk_golden.py

Freezes the megakernel trunk's level-2 role-map quad (interior / last_row /
last_col / corner, 28x28 int32 words each) over one deterministic 112x112
frame with smallNet's seeded params, in BOTH deployed formats: Q16.16 and
Q8.8.  Like sweep_golden.json, the file stores its inputs (the Q16.16
parameter and frame words; the Q8.8 run quantizes the same float values)
and the generator and tests read them from there — see gen_sweep_golden.py
for how they were drawn.  Generation cross-checks four independent routes
per format and fails loudly on any disagreement:

  * the one-launch megakernel on the emulated "fixed" backend vs on
    "fixed_pallas" (same kernel, both substrate plumbings);
  * the megakernel vs the composed per-stage FcnSweep cascade
    (`composed_feature_maps` — the decomposition the frozen
    sweep_golden.json already pins);
  * the megakernel vs the untiled numpy int64 oracle
    (kernels/frame_trunk/ref.py), which knows nothing about tiles, halos,
    or window offsets.

So the frozen vectors pin the megakernel's tiling/halo bookkeeping against
vectors that cannot silently regenerate themselves — the CI golden job
rebuilds this file and diffs it, exactly like sweep_golden.json.
"""
from __future__ import annotations

import json
import pathlib

import jax
import numpy as np
from gen_sweep_golden import decode_inputs, golden_inputs

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.kernels.frame_trunk.ref import frame_trunk_quad_ref
from repro.streaming.fcn_sweep import (composed_feature_maps,
                                       sweep_feature_maps)

MAPS = ("interior", "last_row", "last_col", "corner")
FORMATS = {"q16_16": fxp.Q16_16, "q8_8": fxp.Q8_8}
PATH = pathlib.Path(__file__).parent / "frame_trunk_golden.json"


def _check_equal(name, a, b):
    if not np.array_equal(np.asarray(a, np.int64), np.asarray(b, np.int64)):
        raise SystemExit(f"substrate drift while generating {name!r}")
    return np.asarray(a, np.int64)


def main() -> None:
    inputs = golden_inputs(PATH)
    params, pixels = decode_inputs(inputs)

    out = {
        "frame": {"source": "SyntheticVideoSource(n_frames=1, seed=7)",
                  "index": 0, "shape": list(pixels.shape[:2])},
        "inputs": inputs,
        "maps": {},
    }
    for fmt, cfg in FORMATS.items():
        be = B.FixedBackend(name=f"fixed_{fmt}", cfg=cfg)
        bp = B.FixedPallasBackend(name=f"fixed_pallas_{fmt}", cfg=cfg)
        p = be.prepare_params(params)
        if jax.eval_shape(lambda f: be.frame_trunk(f, p),
                          pixels[None]) is None:
            raise SystemExit(f"{fmt}: the one-launch trunk did not apply")
        mega = sweep_feature_maps(params, pixels, backend=be)
        mega_p = sweep_feature_maps(params, pixels, backend=bp)
        comp = composed_feature_maps(params, pixels, backend=be)

        x = np.asarray(be.ingest(pixels[None]))
        oracle = frame_trunk_quad_ref(x[0], np.asarray(p["conv1"]["w"]),
                                      np.asarray(p["conv1"]["b"]),
                                      np.asarray(p["conv2"]["w"]),
                                      np.asarray(p["conv2"]["b"]), cfg)

        out["maps"][fmt] = {}
        for k, name in enumerate(MAPS):
            words = _check_equal(f"{fmt}/{name} (fixed vs fixed_pallas)",
                                 mega[name], mega_p[name])
            _check_equal(f"{fmt}/{name} (megakernel vs composed)",
                         words, comp[name])
            _check_equal(f"{fmt}/{name} (megakernel vs numpy oracle)",
                         words, oracle[k])
            out["maps"][fmt][name] = words.tolist()

    PATH.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {PATH} ({PATH.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
