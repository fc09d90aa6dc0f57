"""Share of its roofline that the `frame_trunk` megakernel reaches (%):
the least time the chip's published peaks allow for the trunk's
operations and bytes (counted from the frame's shape), over the kernel's
device time per frame in the trace. The frame's words make it
memory-bound."""
from chipbench import counts, sweep_ops


def read(run):
    dev = sweep_ops.per_frame_s(run, "trunk")
    if dev is None:
        return None
    t = run.cell.traffic
    least, _ = counts.least_seconds(*counts.trunk(t["height"], t["width"]),
                                    run.peaks)
    return least / dev * 100.0
