"""Share of the traced window in which a collective runs and no other
operation covers it, averaged over the chips: the row-parallel seams'
all-reduces, the routed experts' combine (``seam_moe_combine``), the
embedding's sum and the argmax's gathers."""
from pbench import layers


def read(run):
    return layers.collective_pct(run, exposed=True)
