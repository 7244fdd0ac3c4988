"""Device idle milliseconds inside ``serve.decode.readback`` and
``serve.decode.emit`` (the tokens' readback, then appending them, finish
checks and freed blocks) per decode launch."""
from pbench import spans


def read(run):
    return spans.per_span_ms(run, ["serve.decode.readback",
                                   "serve.decode.emit"],
                             "serve.decode.launch")
