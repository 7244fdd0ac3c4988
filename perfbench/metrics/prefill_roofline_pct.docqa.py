"""Each chip's share of the chunks' least time (the larger of FLOPs over
the bf16 peak and bytes over the HBM peak, from true chunk lengths and the
configuration's own counts, ``counts/deepseek_mla_moe.py``) over their
device time."""
from pbench import layers


def read(run):
    return layers.roofline_pct(run, "chunk")
