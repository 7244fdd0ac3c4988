"""Share of the traced window in which the device sat idle while the host
was inside a scheduler tick (the program's ``sched.tick`` span)."""
from pbench import spans


def read(run):
    return spans.window_pct(run, ["sched.tick"])
