"""Model FLOPs of the prompt and output tokens processed in the traced
window over the window times the four chips' bf16 peak."""
from pbench import layers


def read(run):
    return layers.mfu_pct(run)
