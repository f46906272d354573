"""Share of the traced window in which the chip ran no op."""
from benchlib import layers


def read(ctx):
    return layers.idle_frac(ctx)
