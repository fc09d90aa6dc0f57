"""Plain numpy reference of smallNet's Qm.n fixed-point datapath.

Independent of the program under test: it imports nothing from `src/`.
Every window is scored as its own 28x28 patch, the way the paper's fabric
sees one image: quantize the pixels at the input port, two stages of
2x2 SAME conv (Keras padding: 0 before, 1 after) -> PLAN sigmoid -> 2x2
max pool, then the 49->10 dense layer and the output PLAN sigmoid.

Word semantics (a `Fmt` is the two's-complement word width and the
fraction bits):

  * product: exact int64 a*b, arithmetic shift right by the fraction bits,
    plus bit (frac-1) of the full product when rounding to nearest, then
    wrapped to the word width;
  * sums (MAC accumulate, bias): wraparound in the word width;
  * PLAN: |x| in int32, the four shift-add segments, 1 - y for x < 0.

Aggregation mirrors the detector's contract: confidence = word / 2**frac in
float32, keep windows whose best confidence reaches the threshold, and
greedily accept the strongest (ties on y, then x) unless an accepted one
lies within `min_dist` (Chebyshev, inclusive).
"""
from __future__ import annotations

import dataclasses

import numpy as np

PATCH = 28


@dataclasses.dataclass(frozen=True)
class Fmt:
    """A Qm.n word: `bits` in all (sign included), `frac` of them fraction."""
    bits: int
    frac: int
    round_nearest: bool = True

    @property
    def scale(self) -> float:
        return float(2 ** self.frac)

    @classmethod
    def of(cls, f: dict) -> "Fmt":
        """From a configuration file's `format` / `control_format`."""
        return cls(f["total_bits"], f["frac_bits"], f["round_nearest"])


def wrap(x: np.ndarray, bits: int) -> np.ndarray:
    """Two's-complement wrap of int64 values to `bits` (sign-extended)."""
    half = np.int64(1) << np.int64(bits - 1)
    mask = (np.int64(1) << np.int64(bits)) - np.int64(1)
    return ((x + half) & mask) - half


def _shift_round(x: np.ndarray, k: int, rn: bool) -> np.ndarray:
    if k == 0 or not rn:
        return x >> k
    return (x >> k) + ((x >> (k - 1)) & 1)


def quantize(x, fmt: Fmt) -> np.ndarray:
    """float32 -> words: round half to even, clip to the word's range."""
    lo, hi = -(2 ** (fmt.bits - 1)), 2 ** (fmt.bits - 1) - 1
    s = np.round(np.asarray(x, np.float32) * np.float32(fmt.scale))
    s = np.clip(s, np.float32(lo), np.float32(hi))
    return wrap(s.astype(np.int64), fmt.bits)


def _const(v: float, fmt: Fmt) -> int:
    return int(quantize(np.float32(v), fmt))


def mul(a: np.ndarray, b, fmt: Fmt) -> np.ndarray:
    return wrap(_shift_round(a * b, fmt.frac, fmt.round_nearest), fmt.bits)


def plan(x: np.ndarray, fmt: Fmt) -> np.ndarray:
    ax = wrap(np.abs(x), 32)
    rn = fmt.round_nearest
    one = _const(1.0, fmt)
    y = np.where(
        ax >= _const(5.0, fmt), one,
        np.where(ax >= _const(2.375, fmt),
                 _shift_round(ax, 5, rn) + _const(0.84375, fmt),
                 np.where(ax >= one,
                          _shift_round(ax, 3, rn) + _const(0.625, fmt),
                          _shift_round(ax, 2, rn) + _const(0.5, fmt))))
    return wrap(np.where(x < 0, one - y, y), 32)


def conv_plan_pool(x: np.ndarray, w4, b: int, fmt: Fmt) -> np.ndarray:
    """(N, h, w) words -> (N, h/2, w/2): 2x2 SAME conv + bias, PLAN, pool."""
    xp = np.pad(x, ((0, 0), (0, 1), (0, 1)))
    h, w = x.shape[1], x.shape[2]
    acc = (mul(xp[:, :h, :w], w4[0], fmt) + mul(xp[:, :h, 1:], w4[1], fmt)
           + mul(xp[:, 1:, :w], w4[2], fmt) + mul(xp[:, 1:, 1:], w4[3], fmt))
    y = plan(wrap(acc + b, fmt.bits), fmt)
    return np.maximum(np.maximum(y[:, ::2, ::2], y[:, ::2, 1::2]),
                      np.maximum(y[:, 1::2, ::2], y[:, 1::2, 1::2]))


def quantize_params(params: dict, fmt: Fmt) -> dict:
    """Float params (the harness's seeded draw) -> the words the fabric
    would hold: conv taps row-major over the 2x2 kernel."""
    q = {k: {n: quantize(v, fmt) for n, v in layer.items()}
         for k, layer in params.items()}
    return {"w1": q["conv1"]["w"].reshape(4), "b1": int(q["conv1"]["b"][0]),
            "w2": q["conv2"]["w"].reshape(4), "b2": int(q["conv2"]["b"][0]),
            "wd": q["dense"]["w"], "bd": q["dense"]["b"]}


def score_patches(words: np.ndarray, qp: dict, fmt: Fmt) -> np.ndarray:
    """(N, 28, 28) input words -> (N, 10) output words."""
    f = conv_plan_pool(words, qp["w1"], qp["b1"], fmt)
    f = conv_plan_pool(f, qp["w2"], qp["b2"], fmt)
    f = f.reshape(len(f), -1)                              # (N, 49)
    acc = np.zeros((len(f), qp["wd"].shape[1]), np.int64)
    for j in range(f.shape[1]):                            # 49 MAC columns
        acc += mul(f[:, j:j + 1], qp["wd"][j][None, :], fmt)
    return plan(wrap(acc + qp["bd"][None, :], fmt.bits), fmt)


def window_positions(H: int, W: int, stride: int) -> list[tuple[int, int]]:
    """Top-left of every 28x28 window, row-major; the last row and column
    are clamped to the frame edge."""
    ys = list(range(0, H - PATCH, stride)) + [H - PATCH]
    xs = list(range(0, W - PATCH, stride)) + [W - PATCH]
    return [(y, x) for y in ys for x in xs]


def score_frame(frame: np.ndarray, params: dict, fmt: Fmt,
                positions, block: int = 2048) -> np.ndarray:
    """(H, W) float32 frame -> (n_windows, 10) words, every window scored
    as its own patch, in blocks of windows so memory stays small."""
    qp = quantize_params(params, fmt)
    words = quantize(frame, fmt)
    view = np.lib.stride_tricks.sliding_window_view(words, (PATCH, PATCH))
    ys = np.asarray([p[0] for p in positions], np.intp)
    xs = np.asarray([p[1] for p in positions], np.intp)
    out = np.empty((len(positions), 10), np.int64)
    for s in range(0, len(positions), block):
        b = slice(s, s + block)
        out[b] = score_patches(view[ys[b], xs[b]], qp, fmt)
    return out


def score_images(images: np.ndarray, params: dict, fmt: Fmt,
                 block: int = 2048) -> np.ndarray:
    """(N, 28, 28[, 1]) float32 images -> (N, 10) words."""
    qp = quantize_params(params, fmt)
    imgs = np.asarray(images, np.float32).reshape(-1, PATCH, PATCH)
    out = np.empty((len(imgs), 10), np.int64)
    for s in range(0, len(imgs), block):
        out[s:s + block] = score_patches(quantize(imgs[s:s + block], fmt),
                                         qp, fmt)
    return out


def detections(words: np.ndarray, positions, fmt: Fmt, *,
               threshold: float, min_dist: int) -> list[tuple]:
    """Window words -> [(label, confidence, y, x)], strongest first."""
    conf = words.astype(np.float32) / np.float32(fmt.scale)
    labels = conf.argmax(axis=-1)
    best = conf.max(axis=-1)
    keep = np.nonzero(best >= np.float32(threshold))[0]
    hits = sorted(((float(best[i]), positions[i][0], positions[i][1],
                    int(labels[i])) for i in keep),
                  key=lambda h: (-h[0], h[1], h[2]))
    out: list[tuple] = []
    for s, y, x, lab in hits:
        if any(max(abs(y - d[2]), abs(x - d[3])) <= min_dist for d in out):
            continue
        out.append((lab, s, y, x))
    return out
