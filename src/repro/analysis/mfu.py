"""Analytic MFU + bytes-moved model for smallNet's hot paths.

A closed-form model, not a measurement: the chip benchmark (`chipbench/`)
measures time, utilization and roofline share on the device.  The module
holds two halves:

  1. a DEVICE DATABASE (`DEVICE_DB`): per-dtype peak FLOP/s + memory
     bandwidth for the CPU, common GPUs, and TPU generations, in the style
     of PrimeIntellect's `mfu_tracker.py` (device -> generation ->
     flagship peaks).  Lookups are TOTAL: an unknown accelerator raises
     with the known-device list (silent zeros would quietly report MFU=inf
     or 0), while CPU hosts — where Pallas kernels run under the
     interpreter — always fall back to the generic "cpu" entry (`resolve`).
     `analysis/roofline.py` reads its v5e peaks from here.

  2. an ANALYTIC WORKLOAD MODEL (`trunk_workload` / `sweep_workload` /
     `tiler_workload` / `deployed_workload`): model FLOPs and bytes moved
     per frame for each route — the host tiler, the composed quad-cascade
     sweep, and the `kernels/frame_trunk` one-launch trunk (whose input
     bytes are the real halo'd HBM->VMEM tile DMA traffic, via
     `choose_tile`).

FLOP convention: MODEL FLOPs — 2 flops per multiply-accumulate of the
convs and dense layers the route's algorithm specifies, padding taps
included (the datapath multiplies them against real zero operands),
activations / bias adds / pool comparisons excluded — NOT the HLO
instruction count (Pallas launches are opaque to HLO).

Bytes-moved convention: off-chip traffic between kernel launches.  The
composed sweep round-trips every intermediate role map through HBM (each
launch reads its inputs and writes its outputs), the one-launch trunk
moves only the halo'd input tiles in and the pooled quad out.

Clock convention (`mfu_clock`): on real accelerators, a measured wall
time; under interpret-mode emulation, the roofline floor
`modeled_seconds` — emulator wall time is not a device clock.  The basis
string says which one produced a value.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

# ---------------------------------------------------------------------------
# Device database
# ---------------------------------------------------------------------------

DTYPE_CLASSES = ("f32", "bf16", "f16", "int8", "int32")


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Peak rates for one device: FLOP/s per dtype class + HBM/DRAM
    bandwidth in bytes/s.  `kinds` are substrings matched (case-insensitive)
    against `jax.Device.device_kind` by `lookup`."""
    name: str
    kinds: tuple[str, ...]
    peak_flops: Mapping[str, float]
    mem_bw: float
    source: str

    def peak(self, dtype: str) -> float:
        if dtype not in self.peak_flops:
            raise KeyError(
                f"device {self.name!r} has no peak for dtype class "
                f"{dtype!r}; known: {sorted(self.peak_flops)}")
        return self.peak_flops[dtype]


def _spec(name, kinds, f32, bf16, f16, i8, i32, bw, source):
    return DeviceSpec(name, kinds,
                      {"f32": f32, "bf16": bf16, "f16": f16,
                       "int8": i8, "int32": i32}, bw, source)


# Vendor-nameplate peaks where published; integer-pipeline and f32-on-MXU
# numbers are order-of-magnitude engineering estimates (flagged per entry).
# TPUs run int32 on the VPU, not the MXU, so the int32 peaks are small —
# which is the honest denominator for this repo's Qm.n substrates.
DEVICE_DB: dict[str, DeviceSpec] = {s.name: s for s in [
    _spec("cpu", ("cpu",),
          2.5e12, 2.5e12, 2.5e12, 5.0e12, 1.2e12, 1.0e11,
          "generic AVX-512 server estimate (16c x 2 FMA x 16 lanes); the "
          "interpret-mode fallback entry — Pallas interpret achieves a "
          "tiny fraction of even this"),
    _spec("tpu-v4", ("TPU v4",),
          69e12, 275e12, 275e12, 275e12, 5.5e12, 1228e9,
          "TPU v4 datasheet; f32/int32 estimated"),
    _spec("tpu-v5e", ("TPU v5 lite", "TPU v5e"),
          49e12, 197e12, 197e12, 394e12, 3.9e12, 819e9,
          "TPU v5e datasheet; f32/int32 estimated"),
    _spec("tpu-v5p", ("TPU v5p", "TPU v5"),
          115e12, 459e12, 459e12, 918e12, 9.2e12, 2765e9,
          "TPU v5p datasheet; f32/int32 estimated"),
    _spec("tpu-v6e", ("TPU v6 lite", "TPU v6e"),
          230e12, 918e12, 918e12, 1836e12, 18e12, 1640e9,
          "TPU v6e (Trillium) datasheet; f32/int32 estimated"),
    _spec("v100", ("V100",),
          15.7e12, 15.7e12, 125e12, 62.8e12, 15.7e12, 900e9,
          "V100 SXM2 datasheet (no bf16/int8 tensor cores: CUDA-core "
          "rates)"),
    _spec("a100", ("A100",),
          19.5e12, 312e12, 312e12, 624e12, 19.5e12, 2039e9,
          "A100 SXM4-80GB datasheet, dense (no sparsity)"),
    _spec("h100", ("H100",),
          67e12, 989e12, 989e12, 1979e12, 33.5e12, 3352e9,
          "H100 SXM5 datasheet, dense; int32 estimated"),
    _spec("rtx-4090", ("RTX 4090",),
          82.6e12, 165.2e12, 165.2e12, 660.6e12, 41e12, 1008e9,
          "Ada flagship consumer datasheet; int32 estimated"),
]}


def lookup(device_kind: str) -> DeviceSpec:
    """Total device lookup: exact DB key, then case-insensitive substring
    match on each entry's `kinds`.  Unknown devices raise LOUDLY — an MFU
    against a silently-guessed peak is worse than no MFU."""
    if device_kind in DEVICE_DB:
        return DEVICE_DB[device_kind]
    dk = device_kind.lower()
    # longest kind pattern wins so "TPU v5p" never matches the "TPU v5"
    # alias of a different generation first
    best = None
    for spec in DEVICE_DB.values():
        for kind in spec.kinds:
            if kind.lower() in dk and (best is None or len(kind) > best[0]):
                best = (len(kind), spec)
    if best is not None:
        return best[1]
    raise KeyError(
        f"unknown device kind {device_kind!r}: not in the MFU device "
        f"database (known: {sorted(DEVICE_DB)}).  Add a DeviceSpec with "
        f"its per-dtype peaks to analysis/mfu.py — do not let MFU divide "
        f"by a guess.")


def resolve(device=None) -> tuple[DeviceSpec, bool]:
    """(spec, interpret) for the device the process is actually using.
    `device=None` reads jax's default device.  CPU hosts always resolve to
    the generic "cpu" entry (whatever the host CPU's device_kind says) —
    that is the interpret-mode fallback: on CPU every Pallas kernel runs
    under the interpreter, flagged by the returned `interpret` bool."""
    import jax

    from repro.core import runtime
    dev = jax.devices()[0] if device is None else device
    if dev.platform == "cpu":
        return DEVICE_DB["cpu"], runtime.interpret_default()
    return lookup(dev.device_kind), False


# backend name -> (dtype class for the peak denominator, bytes per word
# moved off-chip).  Every registered smallnet backend moves 4-byte words:
# float32 activations or int32 Qm.n words (the int8 backend keeps f32
# activations; only its dense MAC runs int8).
BACKEND_NUMERICS: dict[str, tuple[str, int]] = {
    "ref": ("f32", 4), "plan": ("f32", 4),
    "pallas": ("f32", 4), "pallas_plan": ("f32", 4),
    "fixed": ("int32", 4), "fixed_pallas": ("int32", 4),
    "int8": ("int8", 4),
}


def backend_numerics(backend: str) -> tuple[str, int]:
    if backend not in BACKEND_NUMERICS:
        raise KeyError(
            f"backend {backend!r} has no MFU numerics entry "
            f"(known: {sorted(BACKEND_NUMERICS)})")
    return BACKEND_NUMERICS[backend]


# ---------------------------------------------------------------------------
# Analytic workload model
# ---------------------------------------------------------------------------

PATCH = 28                 # the deployed window side
_HEAD_IN, _HEAD_OUT = 49, 10
_TRUNK_PARAM_WORDS = 10    # 2 convs x (4 taps + 1 bias)
_HEAD_PARAM_WORDS = _HEAD_IN * _HEAD_OUT + _HEAD_OUT
PARAM_WORDS = _TRUNK_PARAM_WORDS + _HEAD_PARAM_WORDS          # 510

# quad-cascade tap counts (streaming/fcn_sweep.py `_sweep_stage`): live
# taps of each masked conv, i.e. the MACs the algorithm specifies
_L0_TAPS = 4 + 2 + 2 + 1             # s_ii + s_li + s_il + s_ll
_L1_TAPS = _L0_TAPS + (4 + 4 + 4 + 2 + 2)   # + s_pi s_ip s_pp s_pl s_lp


@dataclasses.dataclass(frozen=True)
class Workload:
    """Model FLOPs + off-chip bytes for one route over one frame.  Bytes
    are split so scaling laws stay exact: `bytes_params` is the constant
    weight traffic (counted once per frame), everything else scales with
    the frame."""
    name: str
    flops: int
    bytes_in: int
    bytes_out: int
    bytes_params: int

    @property
    def bytes_total(self) -> int:
        return self.bytes_in + self.bytes_out + self.bytes_params

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, FLOPs per byte moved."""
        return self.flops / max(self.bytes_total, 1)

    def __add__(self, other: "Workload") -> "Workload":
        return Workload(f"{self.name}+{other.name}",
                        self.flops + other.flops,
                        self.bytes_in + other.bytes_in,
                        self.bytes_out + other.bytes_out,
                        self.bytes_params + other.bytes_params)


def _conv_flops(h: int, w: int, taps: int) -> int:
    """2 flops per MAC, `taps` MACs per output position."""
    return 2 * taps * h * w


def deployed_workload(word_bytes: int = 4) -> Workload:
    """One 28x28 image through `smallnet.apply`: conv1 over 28x28 SAME (4
    taps), conv2 over 14x14, dense 49->10.  The hand-countable unit cell:
    2*(4*784 + 4*196 + 490) = 8820 model FLOPs."""
    flops = (_conv_flops(PATCH, PATCH, 4)
             + _conv_flops(PATCH // 2, PATCH // 2, 4)
             + 2 * _HEAD_IN * _HEAD_OUT)
    return Workload("deployed", flops,
                    bytes_in=PATCH * PATCH * word_bytes,
                    bytes_out=_HEAD_OUT * word_bytes,
                    bytes_params=PARAM_WORDS * word_bytes)


def trunk_workload(H: int, W: int, route: str = "trunk",
                   word_bytes: int = 4) -> Workload:
    """Model FLOPs + bytes for the conv trunk over one HxW frame.

    route="trunk":  the plain two-stage trunk (`smallnet.conv_trunk`'s
        interior map), perfectly fused: read the frame once, write the
        pooled H/4 x W/4 map once.  This is the roofline IDEAL every
        sweep route is measured against.
    route="sweep_composed":  the quad role-map cascade of
        `fcn_sweep._sweep_stage` — 4 masked convs at level 0 (9 live taps
        per pixel) and 4 single- + 5 mixed-source maps at level 1 (25
        live taps), with every intermediate map round-tripping HBM
        between launches (convs, PLAN units, accumulates, pools).
    route="sweep_megakernel":  the same quad maps computed inside
        `kernels/frame_trunk` tiles: FLOPs cover each tile's halo'd conv
        extents ((th+2)x(tw+2) at level 0 — slightly MORE arithmetic than
        the composed cascade at seams), but the only off-chip traffic is
        the real (th+3)x(tw+3) HBM->VMEM tile DMA in and the pooled quad
        out, via the kernel's own `choose_tile`.
    """
    A = H * W
    w = word_bytes
    if route == "trunk":
        flops = _conv_flops(H, W, 4) + _conv_flops(H // 2, W // 2, 4)
        return Workload("trunk", flops, A * w, (A // 16) * w,
                        _TRUNK_PARAM_WORDS * w)
    if route == "sweep_composed":
        a = A // 4
        flops = 2 * _L0_TAPS * A + 2 * _L1_TAPS * a
        # per-launch HBM round-trips (elements):
        #   level 0: 4 convs read the frame (4A), 3 PLAN units re-read the
        #   un-fused conv outs (3A), pools read interior A + mix 2A +
        #   last-col 2A + corner 4A = 9A -> 16A read;
        #   writes: 4 conv outs + 3 PLAN outs + pooled quad A -> 8A
        #   level 1 (maps of a = A/4 elements): 16 conv launches (4 single
        #   + 12 masked partials) read 16a, 7 accumulate adds read 14a,
        #   8 PLAN units read 8a, pools read 9a -> 47a read;
        #   writes: 16a conv + 7a add + 8a PLAN + a pooled quad -> 32a
        reads = 16 * A + 47 * a
        writes = 8 * A + 32 * a
        return Workload("sweep_composed", flops, reads * w, writes * w,
                        _TRUNK_PARAM_WORDS * w)
    if route == "sweep_megakernel":
        from repro.kernels.frame_trunk.ops import HALO, choose_tile
        th, tw = choose_tile(H, W)
        n_tiles = (H // th) * (W // tw)
        flops = n_tiles * (2 * _L0_TAPS * (th + 2) * (tw + 2)
                           + 2 * _L1_TAPS * (th // 2) * (tw // 2))
        dma_in = n_tiles * (th + HALO) * (tw + HALO)
        quad_out = 4 * (H // 4) * (W // 4)
        return Workload("sweep_megakernel", flops, dma_in * w, quad_out * w,
                        _TRUNK_PARAM_WORDS * w)
    raise ValueError(
        f"unknown trunk route {route!r} "
        f"(known: trunk, sweep_composed, sweep_megakernel)")


def head_workload(n_windows: int, word_bytes: int = 4) -> Workload:
    """The windowed dense head: gather 49 pooled features per window, one
    49->10 MAC per window."""
    w = word_bytes
    return Workload("head", 2 * _HEAD_IN * _HEAD_OUT * n_windows,
                    n_windows * _HEAD_IN * w, n_windows * _HEAD_OUT * w,
                    _HEAD_PARAM_WORDS * w)


def sweep_workload(H: int, W: int, n_windows: int, route: str,
                   word_bytes: int = 4) -> Workload:
    """The full FcnSweep per-frame program: trunk (composed or megakernel
    route) + windowed dense head."""
    return (trunk_workload(H, W, route, word_bytes)
            + head_workload(n_windows, word_bytes))


def tiler_workload(n_windows: int, word_bytes: int = 4) -> Workload:
    """The host-tiler route: every window re-runs the full 28x28 deployed
    network, and every window's 784 pixels are re-read from the frame —
    overlapping windows re-convolve (and re-move) shared pixels, which is
    exactly what the sweep exists to avoid."""
    d = deployed_workload(word_bytes)
    return Workload("tiler", d.flops * n_windows,
                    d.bytes_in * n_windows, d.bytes_out * n_windows,
                    PARAM_WORDS * word_bytes)


ROUTE_WORKLOADS = ("tiler", "sweep_composed", "sweep_megakernel")


def route_workload(route: str, H: int, W: int, n_windows: int,
                   word_bytes: int = 4) -> Workload:
    """One Workload per (route, geometry)."""
    if route == "tiler":
        return tiler_workload(n_windows, word_bytes)
    if route in ("sweep_composed", "sweep_megakernel"):
        return sweep_workload(H, W, n_windows, route, word_bytes)
    raise ValueError(f"unknown route {route!r} "
                     f"(known: {ROUTE_WORKLOADS})")


# ---------------------------------------------------------------------------
# Achieved rates, MFU, roofline terms
# ---------------------------------------------------------------------------

def achieved(workload: Workload, seconds: float) -> dict:
    """Measured rates for one frame of `workload` computed in `seconds`."""
    if not seconds > 0:
        raise ValueError(f"achieved() needs a positive duration, got "
                         f"{seconds!r}")
    return {"achieved_flops": workload.flops / seconds,
            "achieved_bw": workload.bytes_total / seconds}


def mfu(workload: Workload, seconds: float, *, device: DeviceSpec,
        dtype: str) -> float:
    """Model-FLOPs utilization: (model FLOPs / wall seconds) / peak FLOP/s
    of the device at the backend's dtype class.  By construction in (0, 1]
    for any real measurement — a value outside that range means the
    workload model or the device entry is wrong."""
    return achieved(workload, seconds)["achieved_flops"] / device.peak(dtype)


def modeled_seconds(workload: Workload, *, device: DeviceSpec,
                    dtype: str) -> float:
    """Roofline floor time for one frame: max(compute floor, memory floor).
    This is the MFU clock under interpret-mode emulation: on a CPU host
    every Pallas launch runs under the interpreter, so wall time measures
    the INTERPRETER, not the device program the kernel describes — by the
    emulator's clock, round-tripping 2 MB through HBM costs the same as
    DMAing 100 KB once, which would invert every conclusion the bytes
    model exists to surface.  The roofline floor is deterministic and
    machine-independent; on real accelerators the measured clock is used
    instead (`mfu_clock`)."""
    t = roofline_terms(workload, device=device, dtype=dtype)
    return max(t["compute_s"], t["memory_s"])


def mfu_clock(workload: Workload, measured_s: float, *, device: DeviceSpec,
              dtype: str, interpret: bool) -> tuple[float, str]:
    """(seconds, basis) the MFU/achieved-rate columns divide by: the
    measured device-program wall time on real hardware, the roofline floor
    (`modeled_seconds`) under interpret-mode emulation.  The basis string
    ("measured" / "roofline_model") travels with every mfu value so a
    modelled one can never be misread as a hardware measurement."""
    if interpret:
        return modeled_seconds(workload, device=device, dtype=dtype), \
            "roofline_model"
    return measured_s, "measured"


def roofline_terms(workload: Workload, *, device: DeviceSpec,
                   dtype: str) -> dict:
    """Two-term roofline for one frame: compute floor, memory floor, the
    binding term, and the attainable FLOP/s at this arithmetic intensity
    (min(peak, intensity * bw) — the classic roofline ceiling)."""
    peak = device.peak(dtype)
    compute_s = workload.flops / peak
    memory_s = workload.bytes_total / device.mem_bw
    return {
        "flops": workload.flops,
        "bytes": workload.bytes_total,
        "intensity": workload.intensity,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "attainable_flops": min(peak, workload.intensity * device.mem_bw),
        "peak_flops": peak,
        "mem_bw": device.mem_bw,
        "device": device.name,
        "dtype": dtype,
    }
