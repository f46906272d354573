"""Host time per single-frame submit from the copied frame to the
enqueued program: the `serve.dispatch` spans per `serve.submit`
(trace)."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, ("serve.dispatch",))
