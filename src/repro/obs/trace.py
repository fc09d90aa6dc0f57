"""Lightweight runtime spans: the per-frame waterfall of the serving stack.

A `Span` is one timed region on the monotonic clock (`perf_counter` — the
same clock every latency number in the repo is measured on, so span
durations and stats() latencies are directly comparable): name, trace id
(which frame / request it belongs to), span id + parent id (nesting),
tags, and a TERMINAL STATUS.  The status convention is the contract
`recorder.reconcile` checks against the serving ledgers:

  root spans ("frame", "request") end in exactly one terminal state —
  "served", "dropped:<stage>/<reason>", or "shed:<reason>" — matching the
  component's own accounting (pipeline `frames_in == served + dropped`,
  engine `submitted == served + shed + pending`).  Interior spans
  ("infer", "queue_wait", "pipeline.tile", "engine.device_step", ...) end
  "ok" unless the work they cover failed.

Tracing is OFF by default and costs one `trace.get()` (a module attribute
read) + None check per instrumentation site until `trace.enable()` turns
it on; enabling installs a process-wide `Tracer` whose finished spans land
in a bounded `recorder.FlightRecorder` ring.  The `--trace` flag of
`examples/stream_demo.py` is a thin wrapper around `enable()` + a JSONL
dump of the ring.

`region(name)` is the span for synchronous work on one thread.  It always
enters a `jax.profiler.TraceAnnotation(name)` — a no-op without a profiler
session, a host event on the device trace's clock inside one — and, with
tracing on, also records the `Span` in the ring.  Region names are
`<layer>.<part>` (`pipeline.select`, `sweep.fetch`, `engine.post`, ...).
Waits that cross coroutines or threads ("frame", "infer", "request",
"queue_wait") stay ring spans: a profiler annotation must open and close
on one thread with nothing else interleaved.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import TYPE_CHECKING

from jax.profiler import TraceAnnotation

if TYPE_CHECKING:                                      # pragma: no cover
    from repro.obs.recorder import FlightRecorder


@dataclasses.dataclass(slots=True)
class Span:
    """One timed region.  `t_start`/`t_end` are perf_counter seconds;
    `status` is "open" until ended.  Slotted: span construction sits on
    the traced hot path (two spans per engine request), and the dict-free
    layout is worth ~0.5 µs per span there."""
    name: str
    trace_id: str
    span_id: int
    parent_id: int | None
    t_start: float
    t_end: float | None = None
    status: str = "open"
    tags: dict = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float | None:
        return None if self.t_end is None else self.t_end - self.t_start

    @property
    def terminal(self) -> bool:
        """True for a span that records a request's FATE (the states the
        ledger reconciliation counts), not just a timed region."""
        return (self.status == "served" or self.status.startswith("shed:")
                or self.status.startswith("dropped:"))

    def to_dict(self) -> dict:
        d = {"name": self.name, "trace_id": self.trace_id,
             "span_id": self.span_id, "parent_id": self.parent_id,
             "t_start": self.t_start, "t_end": self.t_end,
             "status": self.status}
        if self.tags:
            d["tags"] = self.tags
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        return cls(name=d["name"], trace_id=d["trace_id"],
                   span_id=d["span_id"], parent_id=d.get("parent_id"),
                   t_start=d["t_start"], t_end=d.get("t_end"),
                   status=d.get("status", "open"),
                   tags=d.get("tags", {}))


class Tracer:
    """Hands out spans and pushes finished ones to the flight recorder.
    Span ids are process-unique (an itertools counter — thread-safe under
    the GIL for the single `next()` bytecode); starting/ending a span
    never blocks on anything but the recorder ring append."""

    def __init__(self, recorder: "FlightRecorder"):
        self.recorder = recorder
        self._ids = itertools.count(1)
        self._record = recorder.record        # bound once: end() hot path

    def start(self, name: str, trace_id: str, *,
              parent: Span | None = None, **tags) -> Span:
        return Span(name=name, trace_id=trace_id,
                    span_id=next(self._ids),
                    parent_id=parent.span_id if parent is not None else None,
                    t_start=time.perf_counter(), tags=tags)

    def end(self, span: Span, status: str = "ok", **tags) -> Span:
        if span.t_end is not None:
            raise RuntimeError(f"span {span.name}#{span.span_id} already "
                               f"ended ({span.status!r})")
        span.t_end = time.perf_counter()
        span.status = status
        if tags:
            span.tags.update(tags)
        self._record(span)
        return span

    def emit(self, name: str, trace_id: str, t_start: float, t_end: float,
             status: str = "ok", *, parent: Span | None = None,
             **tags) -> Span:
        """Materialize an already-finished span from timestamps recorded
        elsewhere: one allocation + one ring append, no clock reads.  The
        engine's per-request spans use this — the request path records
        plain floats (t_submit, batch formation, step completion) and the
        spans are built once, at batch completion, OFF the submit critical
        path."""
        # manual slot assignment instead of the dataclass __init__: this
        # runs twice per engine request and the generated __init__'s call
        # overhead is measurable there (~0.7 us/span)
        s = object.__new__(Span)
        s.name = name
        s.trace_id = trace_id
        s.span_id = next(self._ids)
        s.parent_id = parent.span_id if parent is not None else None
        s.t_start = t_start
        s.t_end = t_end
        s.status = status
        s.tags = tags
        self._record(s)
        return s

    def end_at(self, span: Span, t: float, status: str = "ok") -> Span:
        """Fast-path end with a pre-read clock value: hot loops (the engine
        ending a whole batch's request spans at one step boundary) pay one
        perf_counter read and no tag kwargs for the lot.  Tags can be set
        directly on `span.tags` before the call."""
        if span.t_end is not None:
            raise RuntimeError(f"span {span.name}#{span.span_id} already "
                               f"ended ({span.status!r})")
        span.t_end = t
        span.status = status
        self._record(span)
        return span

    def point(self, name: str, trace_id: str, status: str = "ok", *,
              parent: Span | None = None, **tags) -> Span:
        """A zero-duration event span (a dispatch decision, an
        at-the-door shed): started and ended at the same instant."""
        return self.end(self.start(name, trace_id, parent=parent, **tags),
                        status)

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str, *,
             parent: Span | None = None, **tags):
        s = self.start(name, trace_id, parent=parent, **tags)
        try:
            yield s
        except BaseException:
            self.end(s, "error")
            raise
        self.end(s)


# -- the process-wide switch --------------------------------------------------

_TRACER: Tracer | None = None


def enable(capacity: int = 65536, *,
           dump_dir: str | None = None) -> Tracer:
    """Install (or replace) the process-wide tracer over a fresh bounded
    flight-recorder ring.  Returns the tracer (its `.recorder` is where
    dumps come from).  Idempotent in effect — calling again starts a new
    ring."""
    from repro.obs.recorder import FlightRecorder
    global _TRACER
    _TRACER = Tracer(FlightRecorder(capacity=capacity, dump_dir=dump_dir))
    return _TRACER


def disable() -> None:
    global _TRACER
    _TRACER = None


def get() -> Tracer | None:
    """The process-wide tracer, or None when tracing is off.  Every
    instrumentation site is `tr = trace.get()` + `if tr is not None` — the
    whole cost of the subsystem when disabled."""
    return _TRACER


# -- regions: profiler annotation + ring span --------------------------------

class region:
    """Context manager for one synchronous region of work on this thread:

        with trace.region("sweep.fetch", parent=score_span) as span:
            if span is not None:
                span.tags["n"] = n
            ...

    Always a `jax.profiler.TraceAnnotation(name)`; without a profiler
    session entering and leaving a region costs 0.83 us on a TPU v5e
    host and 0.33-0.39 us on an x86 CPU host (0.36 and 0.16-0.17 us for a
    `contextlib.nullcontext`).  With tracing on it also records a ring
    span named `name` — trace id `trace_id`, else the parent's, else
    `name` — that ends "error" when the body raises.  The span (None
    when tracing is off) is what `with` binds: callers set its tags
    inside, so nothing is built for them when tracing is off.  A class,
    not a generator context manager, which costs 1.77 and 0.82 us on
    those hosts."""

    __slots__ = ("_ann", "_tracer", "_name", "_trace_id", "_parent", "span")

    def __init__(self, name: str, trace_id: str | None = None, *,
                 parent: Span | None = None):
        self._name = name
        self._trace_id = trace_id
        self._parent = parent
        self.span = None

    def __enter__(self) -> Span | None:
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        tr = self._tracer = _TRACER
        if tr is not None:
            p = self._parent
            tid = self._trace_id or (p.trace_id if p is not None
                                     else self._name)
            self.span = tr.start(self._name, tid, parent=p)
        return self.span

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.span is not None:
            self._tracer.end(self.span,
                             "ok" if exc_type is None else "error")
        self._ann.__exit__(exc_type, exc, tb)
