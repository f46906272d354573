"""Host time per submit in ShardDispatcher's own work: padding, the
shard split, the poison scan, health and breaker bookkeeping; the self
time of the `serve.fanout` span, its transfer, dispatch and wait spans
left out (trace)."""
from benchlib import spans


def read(ctx):
    return spans.self_ms(ctx.trace, "serve.fanout")
