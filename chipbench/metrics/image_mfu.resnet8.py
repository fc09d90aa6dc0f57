"""The whole ResNet-8 step's share of the chip's published integer peak
(%): model operations per image (`counts_resnet8.model_ops_per_image`:
2 per multiply-accumulate, plus bias, residual and pool adds) times the
images the traced window served per second, over 393 TOP/s. Read only
where the trace holds the conv kernel, so a program without ResNet-8
reads nothing."""
from chipbench import counts_resnet8, resnet8_ops


def read(run):
    n = resnet8_ops.served(run)
    if not n or not run.trace or not resnet8_ops.conv_seconds(run):
        return None
    ops = counts_resnet8.model_ops_per_image() * n
    return ops / run.trace["window_s"] / run.peaks["int_ops"] * 100.0
