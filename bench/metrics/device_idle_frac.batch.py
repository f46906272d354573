"""Share of the traced window in which the cell's chips ran no op,
averaged over the chips."""
from benchlib import layers


def read(ctx):
    return layers.idle_frac(ctx)
