"""The benchmark's own generator: weights, frames, images and arrivals.

Everything is a pure function of the seed (numpy `default_rng` keyed by
the seed and a role tag), so the same seed gives the same inputs on any
machine. Nothing here imports the program.

The digit glyphs, the box blur and the 28x28 image recipe are copies of
`repro.data.synth_mnist` and `repro.streaming.loadgen.LoadGen.image`; the
arrival schedules follow `LoadGen`, except that a Poisson window holds a
fixed number of arrivals (uniform order statistics: a Poisson process
conditioned on its count), so every seed offers the same work.
"""
from __future__ import annotations

import numpy as np

PATCH = 28

_GLYPHS = {
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00110", "01000", "10000", "11111"],
    3: ["11110", "00001", "00001", "01110", "00001", "00001", "11110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}
# kron upscale factors of a glyph cell: digits 14..28 px tall, so every
# digit fits one 28x28 window
_SCALES = (2, 3, 4)


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, *tags])


def glyph(d: int) -> np.ndarray:
    return np.array([[int(c) for c in row] for row in _GLYPHS[d]], np.float32)


def smooth(img: np.ndarray) -> np.ndarray:
    """3x3 box blur."""
    p = np.pad(img, 1)
    return (p[:-2, :-2] + p[:-2, 1:-1] + p[:-2, 2:] +
            p[1:-1, :-2] + p[1:-1, 1:-1] + p[1:-1, 2:] +
            p[2:, :-2] + p[2:, 1:-1] + p[2:, 2:]) / 9.0


def params(seed: int) -> dict:
    """smallNet's 510 float32 parameters from the seed, drawn so that the
    untrained network still detects digits: each conv kernel sums
    positive taps against a negative bias (it responds to ink, not to the
    blank canvas), and the output layer is N(0, 0.1) plus, for each class,
    the direction in which its centred glyph moves the 49 pooled features,
    scaled so that the glyph lifts its class by 5 before the output
    sigmoid. The output biases put a blank window at -2 (confidence 1/8
    under PLAN). Without this, random weights would light up every window
    or none depending on the seed, and the detector's host work with it.
    Every weight stays far inside the narrowest word's range."""
    rng = rng_for(seed, 0x9A7A)
    p = {c: {"w": rng.uniform(0.5, 1.5, (2, 2, 1, 1)).astype(np.float32),
             "b": rng.uniform(-2.5, -1.5, (1,)).astype(np.float32)}
         for c in ("conv1", "conv2")}
    p["dense"] = {"w": (0.1 * rng.standard_normal((49, 10))).astype(
        np.float32), "b": np.zeros((10,), np.float32)}
    blank = _features(np.zeros((PATCH, PATCH), np.float32), p)
    for c in range(10):
        d = _features(_centred_digit(c), p) - blank
        p["dense"]["w"][:, c] += (5.0 * d / max(float(d @ d), 1e-6)).astype(
            np.float32)
    p["dense"]["b"] = (np.float32(-2.0) - blank @ p["dense"]["w"]).astype(
        np.float32)
    return p


def _centred_digit(c: int) -> np.ndarray:
    g = smooth(np.pad(np.kron(glyph(c), np.ones((3, 3), np.float32)),
                      ((3, 4), (6, 7))))
    return g.astype(np.float32)


def _features(x: np.ndarray, p: dict) -> np.ndarray:
    """The 49 pooled features of one 28x28 window, in float32 with the
    PLAN sigmoid: the float counterpart of the fixed datapath."""
    def stage(x, w, b):
        xp = np.pad(x, ((0, 1), (0, 1)))
        h, wd = x.shape
        k = w.reshape(4)
        y = (xp[:h, :wd] * k[0] + xp[:h, 1:] * k[1] + xp[1:, :wd] * k[2]
             + xp[1:, 1:] * k[3] + b[0])
        ay = np.abs(y)
        s = np.where(ay >= 5, 1.0, np.where(
            ay >= 2.375, 0.03125 * ay + 0.84375, np.where(
                ay >= 1, 0.125 * ay + 0.625, 0.25 * ay + 0.5)))
        s = np.where(y < 0, 1 - s, s)
        return np.maximum(np.maximum(s[::2, ::2], s[::2, 1::2]),
                          np.maximum(s[1::2, ::2], s[1::2, 1::2]))
    x = stage(x, p["conv1"]["w"], p["conv1"]["b"])
    x = stage(x, p["conv2"]["w"], p["conv2"]["b"])
    return x.reshape(-1).astype(np.float32)


def frame(seed: int, i: int, H: int, W: int, n_digits: int,
          noise: float = 0.03) -> np.ndarray:
    """Frame `i` of the seed's ring: `n_digits` glyphs at random places,
    scales and intensities on a dark noisy canvas, (H, W) float32 in
    [0, 1]."""
    rng = rng_for(seed, 0xF4A3, i)
    canvas = np.zeros((H, W), np.float32)
    for _ in range(n_digits):
        s = int(rng.choice(_SCALES))
        g = np.kron(glyph(int(rng.integers(0, 10))),
                    np.ones((s, s), np.float32)) * np.float32(
                        rng.uniform(0.8, 1.0))
        gh, gw = g.shape
        y = int(rng.integers(0, H - gh + 1))
        x = int(rng.integers(0, W - gw + 1))
        canvas[y:y + gh, x:x + gw] = np.maximum(canvas[y:y + gh, x:x + gw], g)
    canvas = smooth(canvas)
    canvas += rng.normal(0, noise, (H, W)).astype(np.float32)
    return np.clip(canvas, 0.0, 1.0).astype(np.float32)


def image(seed: int, i: int) -> np.ndarray:
    """28x28x1 digit `i` of the seed's image ring (the `LoadGen.image`
    recipe: kron upscale, jitter, blur, noise)."""
    rng = rng_for(seed, 0x1A6E, i)
    g = glyph(int(rng.integers(0, 10)))
    big = np.kron(g, np.ones((3, int(rng.integers(3, 5))), np.float32))
    h, w = big.shape
    big = big * np.float32(rng.uniform(0.8, 1.0))
    dy = int(rng.integers(0, PATCH - h + 1))
    dx = int(rng.integers(0, PATCH - w + 1))
    canvas = np.zeros((PATCH, PATCH), np.float32)
    canvas[dy:dy + h, dx:dx + w] = big
    canvas = smooth(canvas)
    canvas += rng.normal(0, 0.03, (PATCH, PATCH)).astype(np.float32)
    return np.clip(canvas, 0.0, 1.0).astype(np.float32)[..., None]


def arrivals(seed: int, process: str, rate: float, seconds: float,
             **kw) -> np.ndarray:
    """Sorted arrival offsets (s) of an open loop at `rate` per second
    over `seconds`. `poisson`: exactly round(rate * seconds) arrivals,
    uniform on the window. `bursty`: `LoadGen`'s interrupted Poisson
    (ON windows of mean `burst_on_s` at rate/duty, silent OFF windows of
    mean `burst_off_s`), thinned or topped up to the same fixed count."""
    n = int(round(rate * seconds))
    rng = rng_for(seed, 0xA221)
    if process == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    if process == "bursty":
        on_s, off_s = float(kw.get("burst_on_s", 0.25)), float(
            kw.get("burst_off_s", 0.75))
        duty = on_s / (on_s + off_s)
        spans, t, on = [], 0.0, bool(rng.uniform() < duty)
        while t < seconds:
            win = rng.exponential(on_s if on else off_s)
            if on:
                spans.append((t, min(t + win, seconds)))
            t += win
            on = not on
        if not spans:
            spans = [(0.0, seconds)]
        lens = np.asarray([b - a for a, b in spans])
        pick = rng.choice(len(spans), size=n, p=lens / lens.sum())
        starts = np.asarray([spans[k][0] for k in pick])
        return np.sort(starts + rng.uniform(0.0, 1.0, n) * lens[pick])
    raise ValueError(f"unknown arrival process {process!r}")
