"""Host milliseconds per ``Server.step`` call (one decode dispatch and
its readback), mean over the traced window."""
from pbench import layers


def read(run):
    return layers.host_ms(run, "decode")
