"""Device milliseconds per execution of the chunk program
(``models/serve.py`` ``prefill_chunk_step``), from the trace."""
from pbench import layers


def read(run):
    return layers.device_ms(run, "chunk")
