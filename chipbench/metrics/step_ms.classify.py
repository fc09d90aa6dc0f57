"""Mean engine step time over the window (ms): the engine's busy-seconds
counter over its step counter, both deltas across the window."""


def read(run):
    c = run.window.counters
    return c["busy_s"] / c["batches"] * 1e3 if c.get("batches") else None
