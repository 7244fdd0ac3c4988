"""Device idle milliseconds inside ``serve.decode.launch`` (the host's
input arrays, their transfers and the decode program's enqueue) per decode
launch."""
from pbench import spans


def read(run):
    return spans.per_span_ms(run, ["serve.decode.launch"],
                             "serve.decode.launch")
