"""Device time per engine step of ResNet-8's conv kernel (ms): the
`_fixed_conv_mc_jit` ops of each run of the engine's step program in the
trace, summed per step and averaged over the steps."""
from chipbench import resnet8_ops


def read(run):
    s = resnet8_ops.conv_per_step_s(run)
    return None if s is None else s * 1e3
