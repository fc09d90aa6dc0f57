"""Chip benchmark of smallNet: one cell per run, one JSON line of results.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name from
`BENCHMARK.json` at the checkout root: the configuration's file under
`chipbench/configs/`, the traffic mix's file under `chipbench/traffic/`
(which names its runner under `chipbench/runners/`), and one reader per
per-layer metric under `chipbench/metrics/`. With `--trace 0` the result
carries the cell's end-to-end metrics; with `--trace 1` the same window
runs under the profiler and the result carries its per-layer metrics.

The run refuses to start without a TPU, or with fewer chips than the cell
asks for. Its last line on standard output is the result object; the
numbers that decide `correct` are also the last lines on standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _fail(msg: str, code: int = 2) -> None:
    print(f"chipbench: {msg}", file=sys.stderr)
    sys.exit(code)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = ROOT / "BENCHMARK.json"
    if not manifest.is_file():
        _fail(f"no BENCHMARK.json at {ROOT}")
    sys.path.insert(0, str(ROOT))
    from chipbench import harness
    try:
        cell = harness.load_cell(ROOT, args.workload)
    except (KeyError, FileNotFoundError, ValueError) as e:
        _fail(f"cannot load cell {args.workload!r}: {e}")
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"the program is not in this checkout ({ROOT / 'src'})")

    t_start = time.perf_counter()
    import jax
    t_import = time.perf_counter()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _fail(f"JAX found no devices: {e}")
    t_devices = time.perf_counter()
    dev = devices[0]
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        _fail(f"no TPU: JAX runs on {dev.platform!r}; this benchmark "
              f"measures the chip and has no fallback")
    if len(devices) < cell.chips:
        _fail(f"cell {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))

    runner = harness.load_runner(ROOT, cell)
    state = runner.setup(cell, args.seed, devices[:cell.chips])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace \
        else None
    try:
        t_window = time.perf_counter()
        setup_s = t_window - T_START
        print(f"setup_s={setup_s:.4f} (to harness {t_start - T_START:.4f}, "
              f"import jax {t_import - t_start:.4f}, devices "
              f"{t_devices - t_import:.4f}, cell set-up "
              f"{t_window - t_devices:.4f})", flush=True)
        pauses = harness.watch_gc()
        try:
            window = runner.window(state, args.seconds, trace_dir)
        finally:
            harness.unwatch_gc(pauses)
        window.notes.append(harness.gc_note(pauses))
        memory_peak = harness.memory_peak(devices[:cell.chips])
        reduced = (harness.reduce_trace(trace_dir, cell.chips)
                   if trace_dir else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for line in window.notes:
        print(line, flush=True)
    runner.release(state)
    gc.collect()
    check = runner.check(state, window)

    result = harness.result(cell, args.trace, setup_s, window, check,
                            memory_peak, reduced, devices[:cell.chips])
    for name, value, limit in check.compared:
        print(f"compared {name}={value} limit={limit}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
