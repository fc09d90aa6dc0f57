from repro.kernels.fixed_conv_mc.ops import fixed_conv_mc
from repro.kernels.fixed_conv_mc.ref import fixed_conv_mc_ref
