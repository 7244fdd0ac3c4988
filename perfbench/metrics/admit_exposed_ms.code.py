"""Device idle milliseconds inside the scheduler's admission loop
(``sched.admit``: prefix match and block allocation, failed attempts
included) per request whose final prefill chunk ran in the traced window,
counted from the benchmark's own dispatch records."""
from pbench import spans


def read(run):
    s = spans.exposed_s(run.reduced, ["sched.admit"])
    n = sum(1 for c in run.traced("chunk") if c.info[2])
    return None if s is None or not n else 1e3 * s / n
