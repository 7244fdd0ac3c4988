"""Host milliseconds per ``Server.prefill_chunk`` call, mean over the
traced window."""
from pbench import layers


def read(run):
    return layers.host_ms(run, "chunk")
