"""Host time per single-frame submit in the copy of the frame to the
device and the fetch of its descriptors: the `serve.h2d` and `serve.d2h`
spans per `serve.submit` (trace)."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, ("serve.h2d", "serve.d2h"))
