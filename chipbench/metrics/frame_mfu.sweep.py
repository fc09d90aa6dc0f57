"""The whole sweep's share of the chip's published integer peak (%):
model operations per frame (trunk + head, counted from the shapes) times
the frames the traced window completed per second, over 393 TOP/s."""
from chipbench import counts, sweep_ops


def read(run):
    n = sweep_ops.frames(run)
    if not n or not run.trace:
        return None
    t = run.cell.traffic
    ops = (counts.trunk(t["height"], t["width"])[0]
           + counts.head(sweep_ops.windows(run))[0])
    return ops * n / run.trace["window_s"] / run.peaks["int_ops"] * 100.0
