"""The fused gemm_rs kernel's share of its roofline in the training step
(``kernels/gemm_rs.py``): the least time of the calls a step makes, from the
benchmark's unpadded work counts, over the kernel's device time."""
from pbench import layers


def read(run):
    return layers.kernel_roofline_pct(run, "gemm_rs")
