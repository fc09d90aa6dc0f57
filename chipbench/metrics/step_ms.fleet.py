"""Mean engine step time over the window across the fleet's replicas (ms):
the engines' busy-seconds counters over their step counters, deltas
across the window."""


def read(run):
    c = run.window.counters
    return c["busy_s"] / c["batches"] * 1e3 if c.get("batches") else None
