"""Device time of the stencil kernels (the fused chains of the cell's
task, Pallas ops named `_chain_planes`) per served frame."""
from benchlib import layers


def read(ctx):
    return layers.per_image_ms(ctx, layers.kernel_ns(ctx, layers.STENCIL))
