"""The fused ag_gemm kernel's share of its roofline in the training step
(``kernels/ag_gemm.py``): the least time of the calls a step makes, from the
benchmark's unpadded work counts, over the kernel's device time."""
from pbench import layers


def read(run):
    return layers.kernel_roofline_pct(run, "ag_gemm")
