"""Engine host time per batched submit in admission, bucketing and
padding, stacking and answer assembly: the `serve.admit`, `serve.stack`
and `serve.assemble` spans per `serve.submit` (trace)."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, ("serve.admit", "serve.stack",
                                     "serve.assemble"))
