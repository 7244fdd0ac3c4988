"""Share of the traced window in which no operation ran on the device."""
from pbench import layers


def read(run):
    return layers.idle_pct(run)
