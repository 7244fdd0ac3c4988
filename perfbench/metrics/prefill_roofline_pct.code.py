"""The chunks' least time (the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, from true chunk lengths) over their device time."""
from pbench import layers


def read(run):
    return layers.roofline_pct(run, "chunk")
