"""Device time outside the Pallas kernels (converts, DoG extrema, top-k,
descriptors) per served frame."""
from benchlib import layers


def read(ctx):
    return layers.per_image_ms(ctx, layers.non_pallas_ns(ctx))
