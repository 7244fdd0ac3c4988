"""Share of the traced window in which no operation ran on a device,
averaged over the chips."""
from pbench import layers


def read(run):
    return layers.idle_pct(run)
