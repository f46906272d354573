"""Engine host time per batched submit, outside the engine's own batch
latency: admission, bucketing, stacking and answer assembly (host clock)."""
from benchlib import layers


def read(ctx):
    return layers.admission_ms(ctx)
