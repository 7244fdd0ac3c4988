"""Model FLOPs of the training steps in the traced window (forward and
backward, no recompute) over the window times the chips' bf16 peak."""
from pbench import layers


def read(run):
    return layers.train_mfu_pct(run)
