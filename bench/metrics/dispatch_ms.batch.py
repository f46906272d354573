"""Host time per batched submit from the copied batch to the enqueued
program (tracing, lowering or cache lookup, and enqueue): the
`serve.dispatch` spans per `serve.submit` (trace)."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, ("serve.dispatch",))
