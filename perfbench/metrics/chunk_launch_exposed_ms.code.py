"""Device idle milliseconds inside ``serve.chunk.launch`` (the chunk's
input arrays, their transfers and the chunk program's enqueue) per chunk
launch."""
from pbench import spans


def read(run):
    return spans.per_span_ms(run, ["serve.chunk.launch"],
                             "serve.chunk.launch")
