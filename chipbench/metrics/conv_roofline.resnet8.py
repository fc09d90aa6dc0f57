"""Share of its roofline that ResNet-8's conv kernel reaches (%): the least
time the chip's published peaks allow for the nine conv layers'
operations and bytes over the images the traced window served (counted
from the shapes, `counts_resnet8.convs`), over the device time of the
`_fixed_conv_mc_jit` ops in the window."""
from chipbench import counts, counts_resnet8, resnet8_ops


def read(run):
    dev = resnet8_ops.conv_seconds(run)
    n = resnet8_ops.served(run)
    if not dev or not n:
        return None
    least, _ = counts.least_seconds(*counts_resnet8.convs(n), run.peaks)
    return least / dev * 100.0
