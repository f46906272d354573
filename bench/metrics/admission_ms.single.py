"""Engine host time per single-frame submit, outside the engine's own
batch latency (host clock)."""
from benchlib import layers


def read(ctx):
    return layers.admission_ms(ctx)
