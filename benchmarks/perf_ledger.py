"""Persisted per-PR perf ledger: BENCH_<pr>.json, with MFU + bytes-moved.

Each ledger row pins one (backend, route) of the streaming stack — host
tiler, composed FCN sweep, `kernels/frame_trunk` megakernel sweep — over
the deterministic smoke clip (SyntheticVideoSource seed 7, the same frozen
frames the golden vectors use).  Alongside the PR-6 columns (sustained
FPS, p50/p99 frame latency, drop rate, static launch topology), every row
now carries the roofline account from `analysis/mfu.py`:

    model_flops_per_frame   analytic model FLOPs of the route's algorithm
                            (2/MAC, conv + dense only — NOT HLO counts)
    bytes_per_frame         off-chip bytes the route moves per frame (the
                            megakernel rows count the real halo'd
                            HBM->VMEM tile DMA via `choose_tile`)
    device_ms_per_frame     median direct timing of the route's jitted
                            per-frame device program (pipeline FPS keeps
                            measuring the whole stack; this isolates the
                            per-frame program itself)
    achieved_flops / achieved_bw / mfu / mfu_basis
                            model FLOPs/s, bytes/s, and the fraction of
                            the device-database peak at the backend's
                            dtype class (`DEVICE_DB` lookup is total;
                            unknown devices fail loudly).  The clock these
                            divide by is `mfu_basis`: "measured" wall time
                            on real accelerators, the "roofline_model"
                            floor under interpret-mode emulation — the
                            interpreter's wall clock times the emulator,
                            not the device program, and the modeled floor
                            keeps committed MFU machine-independent (see
                            `analysis/mfu.py::mfu_clock`)

Ledger discovery is per-PR: `--check` gates the NEWEST committed
BENCH_<pr>.json (schema + launch topology + every committed mfu in (0,1]
+ megakernel-vs-composed MFU ordering) against a fresh measurement, and
reports MFU deltas against the PREVIOUS ledger so the perf trajectory is
diffable across PRs.  Launch counts are STATIC (jaxpr traversal) and
machine-independent, so they are pinned exactly; FPS and MFU absolutes are
machine-dependent records — the in-run regression gate remains the
megakernel >= `fps_band` (0.85) of the composed sweep measured in the same
process, plus the structural claim that the megakernel's committed MFU is
strictly higher than the composed cascade's (one launch moving ~20x fewer
bytes must never be the worse-utilized program).

    PYTHONPATH=src python -m benchmarks.perf_ledger --out BENCH_8.json
    PYTHONPATH=src python -m benchmarks.perf_ledger --check   # CI tier-1
"""
from __future__ import annotations

import argparse
import json
import pathlib
import re
import statistics
import sys
import time

FRAMES = 16
SEED = 7
STRIDE = 8
FPS_BAND = 0.85          # megakernel FPS >= band * composed-sweep FPS
SCHEMA_VERSION = 2
TIMING_REPS = 7          # direct device-program timings per row (median)
BACKENDS = ("ref", "fixed", "fixed_pallas")
MEGA_BACKENDS = ("fixed", "fixed_pallas")
ROOT = pathlib.Path(__file__).resolve().parent.parent
_LEDGER_RE = re.compile(r"BENCH_(\d+)\.json$")

ROW_KEYS = ("sustained_fps", "latency_p50_ms", "latency_p99_ms",
            "drop_rate", "trunk_launches_per_frame",
            "program_launches_per_frame")
MFU_KEYS = ("model_flops_per_frame", "bytes_per_frame",
            "device_ms_per_frame", "achieved_flops", "achieved_bw",
            "mfu", "mfu_basis")


def ledger_paths() -> list[pathlib.Path]:
    """All committed BENCH_<pr>.json, oldest PR first."""
    found = []
    for p in ROOT.glob("BENCH_*.json"):
        m = _LEDGER_RE.match(p.name)
        if m:
            found.append((int(m.group(1)), p))
    return [p for _, p in sorted(found)]


def newest_ledger() -> pathlib.Path | None:
    paths = ledger_paths()
    return paths[-1] if paths else None


def previous_ledger() -> pathlib.Path | None:
    paths = ledger_paths()
    return paths[-2] if len(paths) > 1 else None


def _launch_counts(be, params, frame_shape, positions, megakernel):
    """(trunk launches, whole-program launches) for one sweep route —
    static jaxpr counts, identical on every host."""
    import jax.numpy as jnp

    from repro.analysis.launches import count_pallas_launches
    from repro.streaming import fcn_sweep as fs

    H, W = frame_shape
    frame = jnp.zeros((1, H, W, 1), jnp.float32)
    p = be.prepare_params(params)
    trunk = count_pallas_launches(
        lambda f: fs._trunk_quad(be, p, f, megakernel), frame)
    fn = fs._sweep_fn(be, (H, W), 28, tuple(positions), megakernel)
    program = count_pallas_launches(fn, params, frame)
    return trunk, program


def _tiler_launches(be, params, n_windows):
    """Whole-program launches for one host-tiler engine wave (all windows
    of one frame in a single batched `apply`)."""
    import jax.numpy as jnp

    from repro.analysis.launches import count_pallas_launches
    from repro.core import smallnet

    tiles = jnp.zeros((n_windows, 28, 28, 1), jnp.float32)
    return count_pallas_launches(
        lambda t: smallnet.apply(params, t, backend=be), tiles)


def _time_device_program(fn, *args) -> float:
    """Median wall seconds of one call of an already-jitted per-frame
    program: one warmup call (compile), then TIMING_REPS timed calls.
    This is the MFU denominator's clock — the device program alone, no
    pipeline stages, no host tiling."""
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(TIMING_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _route_device_seconds(be, params, frame_shape, positions, route):
    """Direct per-frame device timing for one (backend, route)."""
    import jax
    import jax.numpy as jnp

    from repro.core import smallnet
    from repro.streaming import fcn_sweep as fs

    H, W = frame_shape
    if route == "tiler":
        tiles = jnp.zeros((len(positions), 28, 28, 1), jnp.float32)
        fn = jax.jit(lambda t: smallnet.apply(params, t, backend=be))
        return _time_device_program(fn, tiles)
    frame = jnp.zeros((1, H, W, 1), jnp.float32)
    fn = fs._sweep_fn(be, (H, W), 28, tuple(positions),
                      route == "sweep_megakernel")
    return _time_device_program(fn, params, frame)


def _throughput(params, source, engine, tiler):
    """Best-of-3 unpaced pipeline run (the stream_table throughput idiom,
    one run deeper: the ledger's FPS band is a gate, so one scheduler
    hiccup must not decide it)."""
    from repro.streaming.pipeline import StreamingPipeline
    best = None
    for _ in range(3):
        pipe = StreamingPipeline(source, engine, tiler)
        pipe.run()
        s = pipe.stats()
        if best is None or s["sustained_fps"] > best["sustained_fps"]:
            best = s
    return best


def measure() -> dict:
    """One full ledger measurement: the deterministic smoke config."""
    from repro.analysis import mfu
    from repro.core import backends as B
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming.fcn_sweep import FcnSweep
    from repro.streaming.sources import SyntheticVideoSource

    from benchmarks import latency_table
    from benchmarks.stream_table import _calibrated_tiler, _params

    params = _params()
    source = SyntheticVideoSource(n_frames=FRAMES, seed=SEED)
    H, W = source.frame_shape
    host = _calibrated_tiler(params, source, STRIDE)
    positions = host.positions((H, W))
    device, interpret = mfu.resolve()
    routes = {
        "tiler": host,
        "sweep_composed": FcnSweep(stride=STRIDE, threshold=host.threshold,
                                   megakernel=False),
        "sweep_megakernel": FcnSweep(stride=STRIDE, threshold=host.threshold,
                                     megakernel=True),
    }

    ledger = {
        "config": {"schema_version": SCHEMA_VERSION,
                   "frames": FRAMES, "seed": SEED, "stride": STRIDE,
                   "frame_shape": [H, W], "windows_per_frame": len(positions),
                   "fps_band": FPS_BAND},
        "context": {"deployed_us_per_image":
                    round(latency_table.smoke(params), 1),
                    # machine-dependent provenance for the MFU columns —
                    # recorded, never gated (config above IS gated)
                    "device": device.name,
                    "interpret": interpret,
                    "mem_bw": device.mem_bw},
        "rows": {},
    }
    for name in BACKENDS:
        be = B.get_backend(name)
        dtype, word_bytes = mfu.backend_numerics(name)
        ledger["rows"][name] = {}
        for route, tiler in routes.items():
            if route == "sweep_megakernel" and name not in MEGA_BACKENDS:
                continue   # no megakernel off the fixed substrates
            if route == "tiler":
                trunk, program = None, _tiler_launches(be, params,
                                                       len(positions))
            else:
                trunk, program = _launch_counts(
                    be, params, (H, W), positions,
                    route == "sweep_megakernel")
            eng = VisionEngine(params, backend=name, batch_size=64,
                               warmup=(route == "tiler"))
            s = _throughput(params, source, eng, tiler)
            wl = mfu.route_workload(route, H, W, len(positions), word_bytes)
            dev_s = _route_device_seconds(be, params, (H, W), positions,
                                          route)
            mfu_s, basis = mfu.mfu_clock(wl, dev_s, device=device,
                                         dtype=dtype, interpret=interpret)
            rates = mfu.achieved(wl, mfu_s)
            ledger["rows"][name][route] = {
                "sustained_fps": round(s["sustained_fps"], 1),
                "latency_p50_ms": round(s.get("latency_p50_ms", 0.0), 2),
                "latency_p99_ms": round(s.get("latency_p99_ms", 0.0), 2),
                "drop_rate": round(s["drop_rate"], 3),
                "trunk_launches_per_frame": trunk,
                "program_launches_per_frame": program,
                "model_flops_per_frame": wl.flops,
                "bytes_per_frame": wl.bytes_total,
                "device_ms_per_frame": round(dev_s * 1e3, 3),
                "achieved_flops": round(rates["achieved_flops"], 1),
                "achieved_bw": round(rates["achieved_bw"], 1),
                "mfu": round(mfu.mfu(wl, mfu_s, device=device, dtype=dtype),
                             9),
                "mfu_basis": basis,
            }
    return ledger


def validate(ledger: dict) -> list[str]:
    """Schema gate for a committed ledger: every row carries the full
    column set, every mfu lies in (0, 1], flops/bytes are positive, and
    wherever both sweep routes exist the megakernel's committed MFU is
    strictly higher than the composed cascade's."""
    failures = []
    cfg = ledger.get("config", {})
    if cfg.get("schema_version") != SCHEMA_VERSION:
        failures.append(
            f"ledger schema_version {cfg.get('schema_version')!r} != "
            f"{SCHEMA_VERSION} (regenerate with --out BENCH_<pr>.json)")
    rows = ledger.get("rows", {})
    if not rows:
        failures.append("ledger has no rows")
    for name, routes in rows.items():
        for route, row in routes.items():
            tag = f"{name}/{route}"
            missing = [k for k in ROW_KEYS + MFU_KEYS if k not in row]
            if missing:
                failures.append(f"{tag}: missing columns {missing}")
                continue
            if not 0.0 < row["mfu"] <= 1.0:
                failures.append(
                    f"{tag}: mfu={row['mfu']!r} outside (0, 1] — the "
                    f"workload model or the device-database peak is wrong")
            for key in ("model_flops_per_frame", "bytes_per_frame"):
                if not row[key] > 0:
                    failures.append(f"{tag}: {key}={row[key]!r} must be "
                                    f"positive")
            if row["mfu_basis"] not in ("measured", "roofline_model"):
                failures.append(f"{tag}: unknown mfu_basis "
                                f"{row['mfu_basis']!r}")
        mega, comp = routes.get("sweep_megakernel"), routes.get("sweep_composed")
        if mega is not None and comp is not None and "mfu" in mega \
                and "mfu" in comp and mega["mfu"] <= comp["mfu"]:
            failures.append(
                f"{name}: committed megakernel mfu {mega['mfu']:.3e} <= "
                f"composed {comp['mfu']:.3e} — the one-launch trunk must "
                f"not be the worse-utilized program")
    return failures


def check(ledger: dict, fresh: dict) -> list[str]:
    """Regression gates: committed schema (validate), committed launch
    topology vs fresh static counts EXACTLY — in BOTH directions: a fresh
    row missing from the ledger fails, and a committed row missing from
    the fresh sweep fails too (a backend or route silently dropped from
    the measurement is exactly the regression this gate exists to catch).
    The in-run megakernel-vs-composed FPS ratio must hold the band, and
    fresh mfu values must land in (0, 1] on THIS machine too.  (Committed
    FPS/MFU absolutes are a record, not a gate — rates are
    machine-dependent.)"""
    failures = validate(ledger)
    if ledger.get("config") != fresh["config"]:
        failures.append(f"ledger config drifted: committed "
                        f"{ledger.get('config')} vs {fresh['config']}")
        return failures
    for name, routes in ledger.get("rows", {}).items():
        for route in routes:
            if fresh["rows"].get(name, {}).get(route) is None:
                failures.append(
                    f"committed row {name}/{route} vanished from the fresh "
                    f"measurement (backend/route dropped from the sweep?)")
    for name, routes in fresh["rows"].items():
        for route, row in routes.items():
            committed = ledger["rows"].get(name, {}).get(route)
            if committed is None:
                failures.append(f"ledger misses row {name}/{route}")
                continue
            for key in ("trunk_launches_per_frame",
                        "program_launches_per_frame"):
                if committed.get(key) != row[key]:
                    failures.append(
                        f"{name}/{route}: {key} changed "
                        f"{committed.get(key)} -> {row[key]} (commit a "
                        f"regenerated BENCH_<pr>.json if intentional)")
            if not 0.0 < row["mfu"] <= 1.0:
                failures.append(
                    f"{name}/{route}: freshly measured mfu={row['mfu']:.3e} "
                    f"outside (0, 1] on this machine")
        mega = routes.get("sweep_megakernel")
        if mega is not None:
            if mega["trunk_launches_per_frame"] != 1:
                failures.append(
                    f"{name}: megakernel trunk is "
                    f"{mega['trunk_launches_per_frame']} launches, not 1")
            composed_fps = routes["sweep_composed"]["sustained_fps"]
            if mega["sustained_fps"] < FPS_BAND * composed_fps:
                failures.append(
                    f"{name}: megakernel sweep regressed past the "
                    f"{FPS_BAND:.0%} band: {mega['sustained_fps']:.1f} vs "
                    f"composed {composed_fps:.1f} FPS")
    return failures


def mfu_deltas(previous: dict | None, current: dict) -> list[str]:
    """Cross-PR trajectory diff: one line per (backend, route) shared with
    the previous ledger.  Informational — machine-dependent absolutes are
    never a gate — but this is what makes the perf trajectory readable
    without replaying old PRs."""
    lines = []
    prev_rows = (previous or {}).get("rows", {})
    for name, routes in current.get("rows", {}).items():
        for route, row in routes.items():
            cur = row.get("mfu")
            if cur is None:
                continue
            old = prev_rows.get(name, {}).get(route, {}).get("mfu")
            if old is None:
                lines.append(f"{name}/{route}: mfu={cur:.3e} (no previous)")
            else:
                lines.append(f"{name}/{route}: mfu {old:.3e} -> {cur:.3e} "
                             f"({(cur - old) / old:+.1%})")
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", type=pathlib.Path, default=None,
                    help="measure and write the ledger JSON (commit it as "
                         "BENCH_<pr>.json in the repo root)")
    ap.add_argument("--check", action="store_true",
                    help="re-measure and gate against the newest committed "
                         "BENCH_<pr>.json; reports MFU deltas vs the "
                         "previous ledger")
    args = ap.parse_args()
    from repro.core import runtime
    runtime.init_compile_cache()

    fresh = measure()
    print("name,us_per_call,derived")
    for name, routes in fresh["rows"].items():
        for route, row in routes.items():
            print(f"perf_ledger/{name}_{route},,"
                  f"fps={row['sustained_fps']} "
                  f"p50={row['latency_p50_ms']}ms "
                  f"p99={row['latency_p99_ms']}ms "
                  f"drop_rate={row['drop_rate']} "
                  f"trunk_launches={row['trunk_launches_per_frame']} "
                  f"program_launches={row['program_launches_per_frame']} "
                  f"device_ms={row['device_ms_per_frame']} "
                  f"flops/frame={row['model_flops_per_frame']} "
                  f"bytes/frame={row['bytes_per_frame']} "
                  f"achieved_bw={row['achieved_bw']:.3g}B/s "
                  f"mfu={row['mfu']:.3e} mfu_basis={row['mfu_basis']}")

    failures = []
    if args.check:
        newest = newest_ledger()
        if newest is None:
            failures.append("no committed BENCH_<pr>.json ledger found")
        else:
            committed = json.loads(newest.read_text())
            print(f"perf_ledger/newest,,{newest.name}")
            failures = check(committed, fresh)
            prev = previous_ledger()
            prev_d = json.loads(prev.read_text()) if prev else None
            for line in mfu_deltas(prev_d, committed):
                print(f"perf_ledger/mfu_delta,,"
                      f"vs={prev.name if prev else 'none'} {line}")
    if args.out is not None:
        args.out.write_text(json.dumps(fresh, indent=1) + "\n")
        print(f"perf_ledger/wrote,,{args.out}")

    for f in failures:
        print(f"perf_ledger/FAIL,,{f}")
    print(f"perf_ledger/result,,{'FAIL' if failures else 'OK'}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
