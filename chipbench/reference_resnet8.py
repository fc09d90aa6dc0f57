"""Plain numpy reference of ResNet-8 on the Qm.n fixed-point datapath.

Independent of the program under test: it imports nothing from `src/`.
The word semantics are `chipbench/reference.py`'s (`Fmt`, `quantize`,
`wrap`): pixels are quantized at the input port, every product is the
exact int64 a*b shifted right by the fraction bits with round to nearest
(bit frac-1 of the full product added; written here as + 2**(frac-1)
before the shift, the same integer) and wrapped to the word, sums wrap.
Because every sum wraps, the wrap of each product is left to the wrap of
the sum it enters: the words are the same modulo 2**bits.

The graph (MLPerf Tiny's resnet_v1_eembc, BN folded into the convs):

    stem     conv 3x3 -> ReLU
    stack 1  [conv 3x3 -> ReLU -> conv 3x3] + x -> ReLU
    stack 2  [conv 3x3 /2 -> ReLU -> conv 3x3] + conv 1x1 /2 (x) -> ReLU
    stack 3  the same
    head     per channel: the sum of the 8x8 words, shifted right by 6
             with the same rounding as a product, wrapped; then the
             64 -> 10 dense layer's logit words

Every conv pads as TensorFlow SAME does. Convs run as loops over their
taps and input channels, one (Cout, pixels) plane of products at a time;
blocks of images run on a few threads (numpy releases the
interpreter lock inside its array operations).
"""
from __future__ import annotations

import concurrent.futures
import os

import numpy as np

from chipbench.reference import Fmt, quantize, wrap

STAGES = ("stem", "stack1", "stack2", "stack3", "head")


def quantize_params(params: dict, fmt: Fmt) -> dict:
    """Float params (the harness's seeded, BN-folded draw) -> words."""
    return {name: {k: quantize(v, fmt) for k, v in layer.items()}
            for name, layer in params.items()}


def conv(x: np.ndarray, w: np.ndarray, b: np.ndarray, stride: int,
         fmt: Fmt) -> np.ndarray:
    """(n,H,W,C) words -> (n,Ho,Wo,N) words: TensorFlow SAME k x k conv,
    every product rounded on its own, the sum and the bias wrapped."""
    n, H, W, C = x.shape
    k, N = w.shape[0], w.shape[3]
    Ho, Wo = -(-H // stride), -(-W // stride)
    ph = max((Ho - 1) * stride + k - H, 0)
    pw = max((Wo - 1) * stride + k - W, 0)
    xp = np.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                    (pw // 2, pw - pw // 2), (0, 0)))
    half = np.int64(1) << np.int64(fmt.frac - 1) if fmt.round_nearest \
        else np.int64(0)
    M = n * Ho * Wo
    acc = np.zeros((N, M), np.int64)               # channel-major planes
    prod = np.empty_like(acc)
    for dy in range(k):
        for dx in range(k):
            win = xp[:, dy:dy + (Ho - 1) * stride + 1:stride,
                     dx:dx + (Wo - 1) * stride + 1:stride, :]
            win = np.ascontiguousarray(win.reshape(M, C).T)  # (C, M)
            for c in range(C):
                np.multiply(w[dy, dx, c][:, None], win[c][None, :],
                            out=prod)
                prod += half
                prod >>= fmt.frac
                acc += prod
    acc = acc.T.reshape(n, Ho, Wo, N)
    return wrap(acc + b, fmt.bits)


def relu(x: np.ndarray) -> np.ndarray:
    return np.maximum(x, 0)


def avgpool_shift(x: np.ndarray, fmt: Fmt) -> np.ndarray:
    """(n,H,W,C) -> (n,C): int32 sum of the H*W words, a rounding right
    shift by log2(H*W), wrapped to the word."""
    hw = x.shape[1] * x.shape[2]
    sh = hw.bit_length() - 1
    if 1 << sh != hw:
        raise ValueError(f"pool extent {hw} is not a power of two")
    s = wrap(x.sum(axis=(1, 2)), 32)
    if fmt.round_nearest:
        s = s + (np.int64(1) << np.int64(sh - 1))
    return wrap(s >> sh, fmt.bits)


def dense(x: np.ndarray, w: np.ndarray, b: np.ndarray,
          fmt: Fmt) -> np.ndarray:
    half = np.int64(1) << np.int64(fmt.frac - 1) if fmt.round_nearest \
        else np.int64(0)
    acc = np.zeros((x.shape[0], w.shape[1]), np.int64)
    for j in range(x.shape[1]):
        acc += (x[:, j:j + 1] * w[j][None, :] + half) >> fmt.frac
    return wrap(acc + b[None, :], fmt.bits)


def _block(x: np.ndarray, q: dict, fmt: Fmt, a: str, b: str,
           proj: str | None, stride: int) -> np.ndarray:
    y = relu(conv(x, q[a]["w"], q[a]["b"], stride, fmt))
    y = conv(y, q[b]["w"], q[b]["b"], 1, fmt)
    s = x if proj is None else conv(x, q[proj]["w"], q[proj]["b"], stride,
                                    fmt)
    return relu(wrap(s + y, fmt.bits))


def score_words(words: np.ndarray, q: dict, fmt: Fmt,
                peaks: dict | None = None) -> np.ndarray:
    """(n,32,32,3) input words -> (n,10) logit words. `peaks`, when given,
    collects the largest |word| each stage's output holds."""
    outs = {}
    x = relu(conv(words, q["stem"]["w"], q["stem"]["b"], 1, fmt))
    outs["stem"] = x
    x = outs["stack1"] = _block(x, q, fmt, "s1a", "s1b", None, 1)
    x = outs["stack2"] = _block(x, q, fmt, "s2a", "s2b", "s2p", 2)
    x = outs["stack3"] = _block(x, q, fmt, "s3a", "s3b", "s3p", 2)
    x = outs["head"] = dense(avgpool_shift(x, fmt), q["dense"]["w"],
                             q["dense"]["b"], fmt)
    if peaks is not None:
        for name in STAGES:
            peaks[name] = max(peaks.get(name, 0),
                              int(np.abs(outs[name]).max(initial=0)))
    return x


def score_images(images: np.ndarray, params: dict, fmt: Fmt, *,
                 block: int = 16, workers: int | None = None,
                 peaks: dict | None = None) -> np.ndarray:
    """(N,32,32,3) float32 images -> (N,10) logit words, in blocks of
    images on up to `workers` threads (default: the CPUs, at most 16)."""
    q = quantize_params(params, fmt)
    imgs = np.asarray(images, np.float32)
    starts = range(0, len(imgs), block)
    workers = workers or min(16, os.cpu_count() or 1)
    parts = [{} for _ in starts]

    def run(i, s):
        return score_words(quantize(imgs[s:s + block], fmt), q, fmt,
                           parts[i])

    with concurrent.futures.ThreadPoolExecutor(workers) as ex:
        outs = list(ex.map(run, range(len(starts)), starts))
    if peaks is not None:
        for part in parts:
            for name, v in part.items():
                peaks[name] = max(peaks.get(name, 0), v)
    return np.concatenate(outs) if outs else np.zeros((0, 10), np.int64)
