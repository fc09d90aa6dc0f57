"""Device time per frame of the window head's ops (the window gather,
role selects and `fixed_dense`), from the trace (ms)."""
from chipbench import sweep_ops


def read(run):
    dev = sweep_ops.per_frame_s(run, "head")
    return None if dev is None else dev * 1e3
