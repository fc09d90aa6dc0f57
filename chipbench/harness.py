"""The harness: cells found by name, the trace reduced, the result built.

Nothing in here knows a cell, a configuration, a traffic mix or a metric
by name. `BENCHMARK.json` names them; each lives in a file of its own:

    chipbench/configs/<config>.json      sizes, word format, source
    chipbench/traffic/<traffic>.json     parameters, and the runner
    chipbench/runners/<runner>.py        setup / window / release / check
    chipbench/metrics/<metric>.py        read(run) -> number or None
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import statistics
import sys
import time
from typing import Any

from chipbench import counts


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


@dataclasses.dataclass
class Window:
    """What a runner's measured window gives back."""
    metrics: dict[str, float]              # end-to-end, by name
    attempted: int
    spans: dict[str, list[float]] = dataclasses.field(default_factory=dict)
    counters: dict[str, float] = dataclasses.field(default_factory=dict)
    notes: list[str] = dataclasses.field(default_factory=list)
    outputs: Any = None                    # what `check` compares


@dataclasses.dataclass
class Check:
    failed: int
    compared: list[tuple[str, float, float]]   # (name, value, limit)

    @property
    def correct(self) -> bool:
        return all(v <= lim for _, v, lim in self.compared)


@contextlib.contextmanager
def profiled(trace_dir: str | None):
    """The measured window: under the profiler, with the host annotation
    `bench.window` that marks its edges on the trace's clock, when
    `trace_dir` is given; plain otherwise."""
    if trace_dir is None:
        yield
        return
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()


def watch_gc() -> list:
    """Record the interpreter's garbage-collection pauses: (generation,
    seconds) each. Only observes; collection runs as it would."""
    pauses: list = []
    t = [0.0]

    def cb(phase, info):
        if phase == "start":
            t[0] = time.perf_counter()
        else:
            pauses.append((info["generation"], time.perf_counter() - t[0]))

    gc.callbacks.append(cb)
    pauses.insert(0, cb)
    return pauses


def unwatch_gc(pauses: list) -> None:
    cb = pauses.pop(0)
    if cb in gc.callbacks:
        gc.callbacks.remove(cb)


def gc_note(pauses: list) -> str:
    gen2 = [s for g, s in pauses if g == 2]
    return (f"gc collections={len(pauses)} gen2={len(gen2)} "
            f"max_ms={max((s for _, s in pauses), default=0.0) * 1e3:.4f} "
            f"total_ms={sum(s for _, s in pauses) * 1e3:.4f}")


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}[name]
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    traffic = _load_json(root / "chipbench" / "traffic"
                         / f"{wl['traffic']}.json")
    config = _load_json(root / cfg["file"])

    def applies(m):
        return name in m["workloads"] if "workloads" in m else True

    return Cell(name=name, chips=int(wl["chips"]), config_name=cfg["name"],
                traffic_name=wl["traffic"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def _load_module(path: pathlib.Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_runner(root: pathlib.Path, cell: Cell):
    name = cell.traffic["runner"]
    return _load_module(root / "chipbench" / "runners" / f"{name}.py",
                        f"chipbench_runner_{name}")


def load_reader(root: pathlib.Path, metric: str):
    return _load_module(root / "chipbench" / "metrics" / f"{metric}.py",
                        "chipbench_metric_" + metric.replace(".", "_"))


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — a backend without the API
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

def _xspace_file(trace_dir: str) -> str | None:
    found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    return str(found[-1]) if found else None


def _op_name(hlo_text: str) -> str:
    """An op event's name is its HLO text, `%fusion.2 = s32[...] ...`:
    keep the instruction's name."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load_trace(trace_dir: str) -> dict:
    """The profiler's xplane as plain data. Per TPU plane, the ops of its
    "XLA Ops" line as (instruction name, module, run, start_ns, dur_ns),
    where module and run name the program run on its "XLA Modules" line
    that holds the op (run -1: none); and the host's `bench.*` annotations
    as (name, start_ns, dur_ns). Kept apart from the reduction so that a
    recorded trace in this shape can be reduced without a profiler."""
    from jax.profiler import ProfileData
    path = _xspace_file(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no xplane file under {trace_dir}")
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            runs, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    runs = [(ev.name.split("(", 1)[0], int(ev.start_ns),
                             int(ev.duration_ns)) for ev in line.events]
                elif line.name == "XLA Ops":
                    ops = [(_op_name(ev.name), int(ev.start_ns),
                            int(ev.duration_ns)) for ev in line.events]
            runs.sort(key=lambda r: r[1])
            starts = [r[1] for r in runs]
            out = []
            for name, s, d in ops:
                k = bisect.bisect_right(starts, s) - 1
                if k < 0 or s >= runs[k][1] + runs[k][2]:
                    k = -1
                out.append([name, runs[k][0] if k >= 0 else "", k, s, d])
            devices[plane.name] = out
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: dict, chips: int) -> dict:
    """Busy time, per-op totals, program runs and idle gaps inside the
    window that the host annotation `bench.window` marks.

    busy_s is the union of op intervals on each chip, averaged over the
    chips the cell uses; `ops` sums each (module, op) over those chips;
    `runs` lists the first chip's program runs in the window, each as
    (module, [(op, seconds), ...]) in the order the ops ran; an idle gap
    of the first chip is named after the `bench.*` host span that covers
    most of it."""
    win = [h for h in trace["host"] if h[0] == "bench.window"]
    if not win:
        raise ValueError("trace has no bench.window annotation")
    w0, wd = win[-1][1], win[-1][2]
    w1 = w0 + wd
    planes = sorted(trace["devices"])[:chips]
    busy, ops = [], {}
    first_busy, runs = None, {}
    for p in planes:
        iv = []
        for name, module, run, s, d in sorted(trace["devices"][p],
                                              key=lambda o: o[3]):
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            iv.append((s2, e2))
            key = (module, name)
            tot, n = ops.get(key, (0, 0))
            ops[key] = (tot + (e2 - s2), n + 1)
            if first_busy is None and run >= 0:
                runs.setdefault(run, (module, []))[1].append(
                    (name, (e2 - s2) / 1e9))
        merged = _merge(iv)
        busy.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
    gaps = []
    prev = w0
    for s, e in (first_busy or []) + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    spans = [h for h in trace["host"] if h[0] != "bench.window"]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        best, cover = "host.other", 0
        for name, hs, hd in spans:
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        named.append([best, (e - s) / 1e9])
    return {
        "window_s": wd / 1e9,
        "busy_s": statistics.fmean(busy) / 1e9 if busy else 0.0,
        "ops": {k: (t / 1e9, n) for k, (t, n) in ops.items()},
        "runs": [runs[k] for k in sorted(runs)],
        "idle_gaps": named,
    }


def reduce_trace(trace_dir: str, chips: int) -> dict:
    return reduce(load_trace(trace_dir), chips)


# ---------------------------------------------------------------------------
# per-layer metrics and the result line
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader sees."""
    cell: Cell
    window: Window
    trace: dict | None
    peaks: dict | None


def result(cell: Cell, traced: int, setup_s: float, window: Window,
           check: Check, memory_peak_bytes, reduced, devices) -> dict:
    root = pathlib.Path(__file__).resolve().parents[1]
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak_bytes}
    metrics: dict[str, dict] = {}
    out = {"correct": check.correct, "attempted": window.attempted,
           "failed": check.failed, "metrics": metrics, "device": device}
    if not traced:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        for m in cell.end_to_end:
            if m["name"] in window.metrics:
                metrics[m["name"]] = {"value": window.metrics[m["name"]],
                                      "unit": m["unit"]}
    else:
        run = Run(cell=cell, window=window, trace=reduced,
                  peaks=counts.peaks(dev.device_kind))
        for m in cell.per_layer:
            v = load_reader(root, m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][0])[:10]
        out["breakdown"] = {
            "device_ops": [[f"{mod}/{name}" if mod else name, t]
                           for (mod, name), (t, _) in top],
            "idle_gaps": reduced["idle_gaps"]}
    out["compared"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in check.compared}
    return out
