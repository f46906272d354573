"""Share of the HBM roofline the stencil kernels reach: the least bytes of
the chains the cell's task runs on a frame (`benchlib.stencil_bytes`) at
the chip's HBM bandwidth, over their device time."""
from benchlib import layers, stencil_bytes


def read(ctx):
    ns = layers.kernel_ns(ctx, layers.STENCIL)
    if not ns:
        return None
    least_s = (stencil_bytes.frame_bytes(ctx.config, ctx.task) * ctx.images
               / ctx.peak("hbm_bytes_per_s"))
    return 100.0 * least_s / (ns / 1e9)
