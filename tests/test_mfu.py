"""analysis/mfu.py: the analytic workload model is hand-countable at the
28x28 unit cell, scales exactly with frame area, brackets the megakernel's
DMA traffic between the fused ideal and the per-window tiler, and the
device database + MFU arithmetic can never silently produce a value
outside (0, 1]."""
import pytest

from repro.analysis import mfu
from repro.analysis.mfu import (BACKEND_NUMERICS, DEVICE_DB, DTYPE_CLASSES,
                                Workload, backend_numerics, lookup,
                                modeled_seconds, mfu_clock, resolve,
                                route_workload, trunk_workload)


# ---------------------------------------------------------------------------
# hand-counted unit cell
# ---------------------------------------------------------------------------

def test_deployed_workload_hand_count():
    """One 28x28 window: conv1 = 4 taps x 28x28, conv2 = 4 taps x 14x14,
    dense 49->10 — 2 flops per MAC."""
    wl = mfu.deployed_workload()
    assert wl.flops == 2 * (4 * 784 + 4 * 196 + 49 * 10) == 8820
    assert wl.bytes_in == 784 * 4
    assert wl.bytes_out == 10 * 4
    assert wl.bytes_params == 510 * 4


def test_trunk_workload_hand_count_28():
    wl = trunk_workload(28, 28, "trunk")
    assert wl.flops == 2 * (4 * 784 + 4 * 196) == 7840
    assert wl.bytes_in == 784 * 4
    assert wl.bytes_out == (784 // 16) * 4


def test_composed_cascade_hand_count_28():
    """Quad role-map cascade: 9 live taps over the full frame at level 0,
    25 live taps over the quarter-area maps at level 1."""
    wl = trunk_workload(28, 28, "sweep_composed")
    assert wl.flops == 2 * 9 * 784 + 2 * 25 * 196 == 23912


def test_tiler_workload_is_windows_times_deployed():
    d = mfu.deployed_workload()
    wl = mfu.tiler_workload(144)
    assert wl.flops == 144 * d.flops
    assert wl.bytes_in == 144 * d.bytes_in     # every window re-reads pixels
    assert wl.bytes_params == d.bytes_params   # weights counted once


# ---------------------------------------------------------------------------
# scaling laws
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", ["trunk", "sweep_composed"])
def test_frame_scaling_is_exactly_area(route):
    """Doubling H and W must scale flops and frame bytes by exactly 4 —
    param bytes are the constant remainder."""
    for base_side, H in ((112, 224), (128, 256), (128, 512)):
        base = trunk_workload(base_side, base_side, route)
        big = trunk_workload(H, H, route)
        k = (H * H) // (base_side * base_side)
        assert big.flops == k * base.flops
        assert big.bytes_in == k * base.bytes_in
        assert big.bytes_out == k * base.bytes_out
        assert big.bytes_params == base.bytes_params


def test_megakernel_bytes_bracketed():
    """The megakernel's input traffic is the real halo'd tile DMA: more
    than the fused ideal (halos re-read seams), far less than the
    per-window tiler (overlapping windows re-read everything)."""
    for H, n_windows in ((112, 144), (512, 3844)):
        ideal = trunk_workload(H, H, "trunk")
        mega = trunk_workload(H, H, "sweep_megakernel")
        tiler = mfu.tiler_workload(n_windows)
        assert ideal.bytes_in < mega.bytes_in < tiler.bytes_in
        # halo'd conv extents also cost slightly MORE arithmetic
        assert mega.flops > trunk_workload(H, H, "sweep_composed").flops


def test_megakernel_dma_matches_choose_tile():
    from repro.kernels.frame_trunk.ops import HALO, choose_tile
    H = W = 512
    th, tw = choose_tile(H, W)
    n_tiles = (H // th) * (W // tw)
    wl = trunk_workload(H, W, "sweep_megakernel")
    assert wl.bytes_in == n_tiles * (th + HALO) * (tw + HALO) * 4
    assert wl.bytes_out == 4 * (H // 4) * (W // 4) * 4


# ---------------------------------------------------------------------------
# device database
# ---------------------------------------------------------------------------

def test_lookup_is_total():
    with pytest.raises(KeyError, match="unknown device kind"):
        lookup("quantum-abacus-9000")
    assert lookup("tpu-v5e").name == "tpu-v5e"              # exact key
    assert lookup("NVIDIA A100-SXM4-80GB").name == "a100"   # substring
    assert lookup("TPU v5 lite").name == "tpu-v5e"          # longest kind
    with pytest.raises(KeyError, match="no peak for dtype"):
        DEVICE_DB["cpu"].peak("fp4")


def test_every_entry_covers_every_dtype_class():
    for spec in DEVICE_DB.values():
        for dt in DTYPE_CLASSES:
            assert spec.peak(dt) > 0
        assert spec.mem_bw > 0


def test_resolve_cpu_is_interpret_fallback():
    spec, interpret = resolve()
    assert spec.name == "cpu"
    assert interpret is True


def test_backend_numerics_total():
    with pytest.raises(KeyError, match="no MFU numerics"):
        backend_numerics("tpu_only_backend")


# ---------------------------------------------------------------------------
# MFU arithmetic
# ---------------------------------------------------------------------------

def test_mfu_in_unit_interval_for_every_backend_and_route():
    """With the interpret-mode clock (the roofline floor), MFU is
    compute_floor / max(floors) — in (0, 1] by construction for every
    registered backend on every ledger route."""
    device = DEVICE_DB["cpu"]
    for backend in BACKEND_NUMERICS:
        dtype, wb = backend_numerics(backend)
        for route in mfu.ROUTE_WORKLOADS:
            wl = route_workload(route, 112, 112, 144, wb)
            t, basis = mfu_clock(wl, 123.0, device=device, dtype=dtype,
                                 interpret=True)
            assert basis == "roofline_model"
            assert t == modeled_seconds(wl, device=device, dtype=dtype)
            val = mfu.mfu(wl, t, device=device, dtype=dtype)
            assert 0.0 < val <= 1.0, (backend, route, val)


def test_mfu_clock_measured_on_real_hardware():
    device = DEVICE_DB["tpu-v5e"]
    wl = route_workload("sweep_megakernel", 112, 112, 144, 4)
    t, basis = mfu_clock(wl, 0.5, device=device, dtype="int32",
                         interpret=False)
    assert (t, basis) == (0.5, "measured")


def test_megakernel_attainable_mfu_beats_composed():
    """The structural claim the ledger gate pins: at the roofline floor,
    the megakernel's ~20x byte reduction turns into strictly higher MFU
    than the composed cascade on every backend."""
    device = DEVICE_DB["cpu"]
    for backend in ("fixed", "fixed_pallas"):
        dtype, wb = backend_numerics(backend)
        vals = {}
        for route in ("sweep_composed", "sweep_megakernel"):
            wl = route_workload(route, 112, 112, 144, wb)
            t = modeled_seconds(wl, device=device, dtype=dtype)
            vals[route] = mfu.mfu(wl, t, device=device, dtype=dtype)
        assert vals["sweep_megakernel"] > vals["sweep_composed"]


def test_achieved_rejects_nonpositive_time():
    wl = Workload("w", 100, 4, 4, 4)
    with pytest.raises(ValueError, match="positive duration"):
        mfu.achieved(wl, 0.0)
