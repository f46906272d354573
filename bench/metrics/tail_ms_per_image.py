"""Device time of the classifier tail's kernels (`bow_quantize_hist`,
`linear_score`) per served frame."""
from benchlib import layers


def read(ctx):
    return layers.per_image_ms(ctx, layers.kernel_ns(ctx, layers.TAIL))
