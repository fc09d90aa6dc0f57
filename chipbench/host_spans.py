"""Host spans of a traced window: what the host did, on the device's clock.

The profiler writes every `jax.profiler.TraceAnnotation` of the process to
its host plane, one line per thread. The program annotates its synchronous
work as `<layer>.<part>` (`obs.trace.region`: `pipeline.*`, `sweep.*`,
`engine.*`); the harness annotates its calls into the program as `bench.*`.

`load` reads the device ops as `harness.load_trace` does, and every such
host annotation with its thread line as (name, start_ns, dur_ns, line).
Over the window that `bench.window` marks:

  host_spans   {name: [seconds, count, self_seconds]} for each program
               span, clipped to the window; self time leaves out the
               program spans nested in it on the same thread line
  idle_gaps    the first chip's longest idle gaps, each named after the
               innermost span (program or `bench.*`) that covers at least
               half of it — the shortest such span — else the span that
               covers most of it, else `host.other`

`harness.reduce` does not call these: its result line reads `bench.*`
spans alone. `chipbench/trace_split.py` prints them for a cell's windows.
"""
from __future__ import annotations

from collections import defaultdict

from chipbench import harness

PROGRAM = ("pipeline.", "sweep.", "engine.")


def load(trace_dir: str) -> dict:
    """`harness.load_trace`'s result, its host list replaced by every
    program and `bench.*` annotation with its thread line. The xplane is
    read a second time for them: `harness.load_trace` takes a directory
    and keeps no thread lines."""
    from jax.profiler import ProfileData
    trace = harness.load_trace(trace_dir)
    pd = ProfileData.from_file(harness._xspace_file(trace_dir))
    host, k = [], 0
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.",) + PROGRAM):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns), k])
                k += 1
    trace["host"] = host
    return trace


def window(trace: dict) -> tuple[int, int]:
    win = [h for h in trace["host"] if h[0] == "bench.window"]
    if not win:
        raise ValueError("trace has no bench.window annotation")
    return win[-1][1], win[-1][1] + win[-1][2]


def clipped(trace: dict) -> list[tuple[str, int, int, int]]:
    """(name, start_ns, end_ns, line) of every host span but the window's,
    clipped to the window. A span recorded without a line is on line 0."""
    w0, w1 = window(trace)
    out = []
    for h in trace["host"]:
        if h[0] == "bench.window":
            continue
        s, e = max(h[1], w0), min(h[1] + h[2], w1)
        if e > s:
            out.append((h[0], s, e, h[3] if len(h) > 3 else 0))
    return out


def host_spans(trace: dict) -> dict[str, list]:
    lines = defaultdict(list)
    for sp in clipped(trace):
        if sp[0].startswith(PROGRAM):
            lines[sp[3]].append(sp)
    out: dict[str, list] = {}
    for spans in lines.values():
        spans.sort(key=lambda sp: (sp[1], -sp[2]))
        selfs = [e - s for _, s, e, _ in spans]
        stack: list[int] = []            # indices of the open spans
        for i, (_, s, e, _) in enumerate(spans):
            while stack and spans[stack[-1]][2] <= s:
                stack.pop()
            if stack and e <= spans[stack[-1]][2]:
                selfs[stack[-1]] -= e - s
            stack.append(i)
        for (name, s, e, _), own in zip(spans, selfs):
            tot = out.setdefault(name, [0.0, 0, 0.0])
            tot[0] += (e - s) / 1e9
            tot[1] += 1
            tot[2] += own / 1e9
    return out


def first_chip(trace: dict) -> list:
    planes = sorted(trace["devices"])
    return trace["devices"][planes[0]] if planes else []


def gaps(trace: dict) -> list[tuple[int, int]]:
    """The first chip's idle intervals inside the window, in order."""
    w0, w1 = window(trace)
    busy = harness._merge([max(s, w0), min(s + d, w1)]
                          for _, _, _, s, d in first_chip(trace)
                          if min(s + d, w1) > max(s, w0))
    out, prev = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    return out


def covering(spans, s: int, e: int) -> list[tuple[str, int, int]]:
    """(name, covered_ns, span_ns) of each span that overlaps [s, e)."""
    out = []
    for name, hs, he, _ in spans:
        c = min(e, he) - max(s, hs)
        if c > 0:
            out.append((name, c, he - hs))
    return out


def name_gap(spans, s: int, e: int) -> str:
    cov = covering(spans, s, e)
    half = [c for c in cov if 2 * c[1] >= e - s]
    if half:
        return min(half, key=lambda c: c[2])[0]
    return max(cov, key=lambda c: c[1])[0] if cov else "host.other"


def idle_gaps(trace: dict, top: int = 10) -> list[list]:
    spans = clipped(trace)
    longest = sorted(gaps(trace), key=lambda g: g[0] - g[1])[:top]
    return [[name_gap(spans, s, e), (e - s) / 1e9] for s, e in longest]

