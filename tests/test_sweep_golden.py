"""Golden-vector regression for the FCN sweep trunk.

tests/golden/sweep_golden.json freezes the Q16.16 words of the full-frame
sweep over a deterministic 112x112 synthetic frame: all four pooled role
maps (interior / last_row / last_col / corner) and the stride-8 window
scores.  The file also stores the inputs (Q16.16 parameter and frame
words), which the tests read from there — so the vectors pin the
arithmetic, not the `jax.random` stream behind `smallnet.seeded_params`.
Both fixed substrates must reproduce every word — any drift in the
masked-weight edge maps, the decomposed accumulation, or the underlying
conv/PLAN/pool arithmetic fails here first, against vectors that cannot
silently regenerate themselves (the CI golden job diffs a fresh
generation).

Regenerate (only after an INTENTIONAL semantics change) with:
    PYTHONPATH=src python tests/golden/gen_sweep_golden.py
"""
import json
import pathlib

import numpy as np
import pytest

from repro.core import fixed_point as fxp
from repro.core import smallnet
from repro.streaming.fcn_sweep import FcnSweep, sweep_feature_maps

_GOLDEN = json.loads(
    (pathlib.Path(__file__).parent / "golden" / "sweep_golden.json").read_text())


@pytest.fixture(scope="module")
def params():
    return smallnet.params_from_words(_GOLDEN["inputs"]["params"])


@pytest.fixture(scope="module")
def frame():
    words = np.asarray(_GOLDEN["inputs"]["frame"], np.int32)
    assert list(words.shape) == _GOLDEN["frame"]["shape"]
    return np.asarray(fxp.from_fixed(words))[..., None]


def _assert_words(got, want, what):
    np.testing.assert_array_equal(
        np.asarray(got, np.int64), np.asarray(want, np.int64),
        err_msg=f"{what}: sweep words drifted from golden vectors")


def test_golden_covers_all_role_maps():
    assert set(_GOLDEN["maps"]) == {"interior", "last_row", "last_col",
                                    "corner"}
    for m in _GOLDEN["maps"].values():
        assert np.asarray(m).shape == (28, 28)


@pytest.mark.parametrize("backend", ("fixed", "fixed_pallas"))
def test_role_maps_golden(params, frame, backend):
    maps = sweep_feature_maps(params, frame, backend=backend)
    for name, want in _GOLDEN["maps"].items():
        _assert_words(maps[name], want, f"{backend}/{name}")


@pytest.mark.parametrize("backend", ("fixed", "fixed_pallas"))
def test_window_scores_golden(params, frame, backend):
    sweep = FcnSweep(stride=_GOLDEN["stride"])
    fb, pos = sweep.extract(frame)
    assert [list(p) for p in pos] == _GOLDEN["positions"]
    got = sweep.score(params, fb, backend=backend)
    _assert_words(got, _GOLDEN["scores"], f"{backend}/scores")
