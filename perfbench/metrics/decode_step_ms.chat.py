"""Device milliseconds per execution of the decode program
(``models/serve.py`` ``decode_step``), from the trace."""
from pbench import layers


def read(run):
    return layers.device_ms(run, "decode")
