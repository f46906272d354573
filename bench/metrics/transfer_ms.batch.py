"""Host time per batched submit in the copy of the frames to the device
(with its layout conversion) and the fetch of the answers: the
`serve.h2d` and `serve.d2h` spans per `serve.submit` (trace)."""
from benchlib import spans


def read(ctx):
    return spans.mean_ms(ctx.trace, ("serve.h2d", "serve.d2h"))
