"""Compile requests per submit: the `jaxcache.request` marks inside each
`serve.submit` span (trace).  A request that finds its program in the
persistent cache counts too: it still traced and lowered."""
from benchlib import spans


def read(ctx):
    return spans.compile_requests_per_submit(ctx.trace)
