"""Golden-vector regression for the trunk megakernel.

tests/golden/frame_trunk_golden.json freezes the megakernel's level-2 quad
words over the deterministic 112x112 synthetic frame in BOTH deployed
formats (Q16.16 and Q8.8), from inputs (Q16.16 parameter and frame words)
stored in the same file.  Both fixed substrates must reproduce every word
through the one-launch route — any drift in the tile chooser, the halo windows,
the in-kernel edge masking, or the underlying arithmetic fails here first,
against vectors that cannot silently regenerate themselves (the CI golden
job diffs a fresh generation).

Regenerate (only after an INTENTIONAL semantics change) with:
    PYTHONPATH=src python tests/golden/gen_frame_trunk_golden.py
"""
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core import backends as B
from repro.core import fixed_point as fxp
from repro.core import smallnet
from repro.streaming.fcn_sweep import sweep_feature_maps

_GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden"
     / "frame_trunk_golden.json").read_text())

_FORMATS = {"q16_16": fxp.Q16_16, "q8_8": fxp.Q8_8}
_MAPS = ("interior", "last_row", "last_col", "corner")


@pytest.fixture(scope="module")
def params():
    return smallnet.params_from_words(_GOLDEN["inputs"]["params"])


@pytest.fixture(scope="module")
def frame():
    words = np.asarray(_GOLDEN["inputs"]["frame"], np.int32)
    assert list(words.shape) == _GOLDEN["frame"]["shape"]
    return np.asarray(fxp.from_fixed(words))[..., None]


def test_golden_covers_both_formats_and_all_maps():
    assert set(_GOLDEN["maps"]) == set(_FORMATS)
    for fmt in _FORMATS:
        assert set(_GOLDEN["maps"][fmt]) == set(_MAPS)
        for m in _GOLDEN["maps"][fmt].values():
            assert np.asarray(m).shape == (28, 28)


@pytest.mark.parametrize("fmt", sorted(_FORMATS))
@pytest.mark.parametrize("kind", ("fixed", "fixed_pallas"))
def test_megakernel_maps_golden(params, frame, fmt, kind):
    cls = B.FixedBackend if kind == "fixed" else B.FixedPallasBackend
    be = cls(name=f"{kind}_{fmt}_golden", cfg=_FORMATS[fmt])
    p = be.prepare_params(params)
    assert jax.eval_shape(lambda f: be.frame_trunk(f, p),
                          frame[None]) is not None   # the one-launch route
    maps = sweep_feature_maps(params, frame, backend=be)
    for name in _MAPS:
        np.testing.assert_array_equal(
            np.asarray(maps[name], np.int64),
            np.asarray(_GOLDEN["maps"][fmt][name], np.int64),
            err_msg=f"{kind}/{fmt}/{name}: megakernel words drifted from "
                    f"golden vectors")
