"""Share of its roofline that the window head reaches (%): the least time
for the 49->10 dense over every window (operations and bytes counted from
the window count), over the device time per frame of the head's ops (the
window gather, role selects and `fixed_dense`) in the trace."""
from chipbench import counts, sweep_ops


def read(run):
    dev = sweep_ops.per_frame_s(run, "head")
    if dev is None:
        return None
    least, _ = counts.least_seconds(*counts.head(sweep_ops.windows(run)),
                                    run.peaks)
    return least / dev * 100.0
