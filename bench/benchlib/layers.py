"""Arithmetic the per-layer metric readers share.  A reader returns None
where it finds nothing to read, never 0."""
from __future__ import annotations

import statistics

from benchlib import trace

STENCIL = ("_chain_planes",)
TAIL = ("bow_quantize_hist", "linear_score")


def admission_ms(ctx):
    """Mean over submits of the client's wall time minus the engine's
    own batch latency."""
    if not ctx.submits:
        return None
    return 1e3 * statistics.fmean((s.end - s.start) - s.served_s
                                  for s in ctx.submits)


def kernel_ns(ctx, prefixes) -> float:
    if ctx.trace is None:
        return 0.0
    return trace.kernel_ns(ctx.trace, ctx.devices, prefixes)


def non_pallas_ns(ctx) -> float:
    if ctx.trace is None:
        return 0.0
    return sum(trace.busy_ns(ctx.trace, d)
               - trace.busy_ns(ctx.trace, d, trace.is_pallas)
               for d in ctx.devices)


def per_image_ms(ctx, ns: float):
    if not ns or not ctx.images:
        return None
    return ns / 1e6 / ctx.images


def idle_frac(ctx):
    if ctx.trace is None:
        return None
    busy = trace.busy_s(ctx.trace, ctx.devices)
    if not busy:
        return None
    return 1.0 - busy / trace.window_s(ctx.trace)
