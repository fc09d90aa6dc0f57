"""Operations and bytes the algorithm needs, and the chip's published peaks.

The counts follow the shapes, not an implementation: copied from
`repro.analysis.mfu.trunk_workload(route="trunk")` and `head_workload`
(2 operations per multiply-accumulate, 4-byte words).

  trunk, one H x W frame: conv1 over H x W and conv2 over H/2 x W/2, 4 taps
      each; read the frame's words once, write the pooled H/4 x W/4 map
      once, read the 10 conv parameters.
  head, n windows: 49 -> 10 dense per window; read 49 features and write
      10 scores per window, read the 500 dense parameters.
"""
from __future__ import annotations

WORD = 4                       # bytes of an int32 Qm.n word
_TRUNK_PARAMS = 10             # 2 convs x (4 taps + 1 bias)
_HEAD_IN, _HEAD_OUT = 49, 10
_HEAD_PARAMS = _HEAD_IN * _HEAD_OUT + _HEAD_OUT

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip. The int8 figure is the chip's published
# integer peak; the datapath's int32 words run on the vector unit, well
# below it, so a share of it is a share of the chip, not of the VPU.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int_ops": 393e12,
                    "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def trunk(H: int, W: int) -> tuple[int, int]:
    """(operations, bytes) of the conv trunk over one H x W frame."""
    ops = 2 * 4 * H * W + 2 * 4 * (H // 2) * (W // 2)
    nbytes = (H * W + (H // 4) * (W // 4) + _TRUNK_PARAMS) * WORD
    return ops, nbytes


def head(n_windows: int) -> tuple[int, int]:
    """(operations, bytes) of the windowed dense head."""
    ops = 2 * _HEAD_IN * _HEAD_OUT * n_windows
    nbytes = (n_windows * (_HEAD_IN + _HEAD_OUT) + _HEAD_PARAMS) * WORD
    return ops, nbytes


def least_seconds(ops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """The roofline's least time and the bound that sets it."""
    c = ops / peak["int_ops"]
    m = nbytes / peak["hbm_bytes_per_s"]
    return (c, "compute") if c >= m else (m, "memory")
