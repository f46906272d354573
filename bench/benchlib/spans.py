"""The program's own host spans in a trace, and what the serving-path
readers compute from them.

`CvEngine.submit` runs under a `serve.submit` span, each of its phases
under one of `PHASES` (the sharded path puts `serve.fanout` around its
transfers and dispatch), and `core/jaxcache` leaves an instant
`jaxcache.request` span at each compile request.  They are host events of
the trace (`benchlib.trace.Trace.host`), on the device's clock.  A
submit's phases and marks are those that lie inside its span.
`mean_ms`, `self_ms` and `compile_requests_per_submit` return None where
the window holds no `serve.submit` span (a program that emits none has
nothing to read), and 0 where the submits hold none of what is asked
for.
"""
from __future__ import annotations

import bisect

from benchlib import trace

SUBMIT = "serve.submit"
PHASES = ("serve.admit", "serve.stack", "serve.h2d", "serve.dispatch",
          "serve.wait", "serve.d2h", "serve.assemble", "serve.fanout")
COMPILE_MARK = "jaxcache.request"


def submits(tr) -> list:
    """The `serve.submit` spans inside the window, by start."""
    if tr is None:
        return []
    lo, hi = tr.window
    return [e for e in tr.host
            if e.name == SUBMIT and lo <= e.start_ns and e.end_ns <= hi]


def by_submit(tr, subs, names) -> list:
    """Per submit of `subs` (which do not overlap: one client), the
    events named in `names` that lie inside its span."""
    starts = [s.start_ns for s in subs]
    out = [[] for _ in subs]
    for e in tr.host:
        if e.name not in names:
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i >= 0 and e.end_ns <= subs[i].end_ns:
            out[i].append(e)
    return out


def mean_ms(tr, names):
    """Mean over submits of the summed durations of their spans named in
    `names`."""
    subs = submits(tr)
    if not subs:
        return None
    groups = by_submit(tr, subs, frozenset(names))
    return sum(e.end_ns - e.start_ns for g in groups for e in g) \
        / 1e6 / len(subs)


def self_ms(tr, name: str):
    """Mean over submits of the self time of their `name` spans: each
    span's duration less the union of the phase spans inside it."""
    subs = submits(tr)
    if not subs:
        return None
    total = 0.0
    for g in by_submit(tr, subs, frozenset(PHASES)):
        for p in g:
            if p.name != name:
                continue
            kids = [(c.start_ns, c.end_ns) for c in g if c is not p
                    and p.start_ns <= c.start_ns and c.end_ns <= p.end_ns]
            total += (p.end_ns - p.start_ns) - sum(
                t - s for s, t in trace.union(kids))
    return total / 1e6 / len(subs)


def compile_requests_per_submit(tr):
    """Mean count per submit of the `jaxcache.request` marks inside its
    span."""
    subs = submits(tr)
    if not subs:
        return None
    return sum(len(g) for g in by_submit(tr, subs, {COMPILE_MARK})) \
        / len(subs)
