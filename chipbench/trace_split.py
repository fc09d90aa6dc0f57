"""Where a cell's host time goes, window by window, on the chip.

    python3 chipbench/trace_split.py --workload <cell> --seed <n> \
        --seconds <s> --windows <k> [--out <file.jsonl>]

Sets the cell up once, as `chipbench/run.py` does, then runs 2k windows,
alternately under the profiler and without it, and prints one JSON object
per window: its end-to-end metrics, the harness's gc note, the compiles
inside it (`jax_compiles`), and, for a traced window, the device's busy
and window seconds, the program's host spans (`host_spans.host_spans`),
the ten longest idle gaps named after the innermost span that holds them,
and every idle gap over 50 ms and every `bench.*` span over 100 ms with
the spans that overlap it. `--out` appends the same lines to a file.

A diagnostic for `PERF.md`: no benchmark cell runs it, and it checks no
outputs. Like `run.py`, it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _compiles() -> tuple[float, float]:
    from repro.obs import metrics as M
    return (M.REGISTRY.counter("jax_compiles").value,
            M.REGISTRY.counter("jax_compile_seconds").value)


def _overlaps(spans, s, e, top=4):
    from chipbench import host_spans as H
    cov = sorted(H.covering(spans, s, e), key=lambda c: -c[1])[:top]
    return [[name, c / 1e9] for name, c, _ in cov]


def split(trace: dict) -> dict:
    """The host split of one loaded trace (`host_spans.load`)."""
    from chipbench import harness
    from chipbench import host_spans as H
    red = harness.reduce(trace | {"host": [h[:3] for h in trace["host"]
                                           if h[0].startswith("bench.")]},
                         1)
    spans = H.clipped(trace)
    long_gaps = [[(e - s) / 1e9, _overlaps(spans, s, e)]
                 for s, e in H.gaps(trace) if e - s > 50e6]
    long_bench = []
    for name, s, e, line in spans:
        if name.startswith("bench.") and e - s > 100e6:
            inner = [sp for sp in spans
                     if sp[3] == line and not sp[0].startswith("bench.")]
            long_bench.append([name, (e - s) / 1e9,
                               _overlaps(inner, s, e)])
    return {"busy_s": red["busy_s"], "window_s": red["window_s"],
            "host_spans": H.host_spans(trace),
            "idle_gaps": H.idle_gaps(trace),
            "gaps_over_50ms": long_gaps,
            "bench_over_100ms": long_bench}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", type=int, default=6)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from chipbench import harness
    cell = harness.load_cell(ROOT, args.workload)
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"trace_split: no TPU (JAX runs on {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    runner = harness.load_runner(ROOT, cell)
    state = runner.setup(cell, args.seed, devices[:cell.chips])
    out = open(args.out, "a") if args.out else None
    try:
        for k in range(2 * args.windows):
            traced = k % 2 == 0
            trace_dir = (tempfile.mkdtemp(prefix="chipbench-split-")
                         if traced else None)
            c0 = _compiles()
            pauses = harness.watch_gc()
            try:
                w = runner.window(state, args.seconds, trace_dir)
            finally:
                harness.unwatch_gc(pauses)
            c1 = _compiles()
            line = {"cell": cell.name, "window": k, "traced": traced,
                    "metrics": w.metrics, "attempted": w.attempted,
                    "gc": harness.gc_note(pauses),
                    "compiles": [c1[0] - c0[0], c1[1] - c0[1]],
                    "notes": w.notes}
            if traced:
                from chipbench import host_spans as H
                try:
                    line.update(split(H.load(trace_dir)))
                finally:
                    shutil.rmtree(trace_dir, ignore_errors=True)
            text = json.dumps(line)
            print(text, flush=True)
            if out:
                out.write(text + "\n")
                out.flush()
    finally:
        if out:
            out.close()
        runner.release(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
