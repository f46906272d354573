"""From a profiler trace to what the per-layer metrics read.

`read(path)` turns an `.xplane.pb` into plain tuples: per device, the ops
of its "XLA Ops" line; on the host, every event of every thread.  The rest
is arithmetic on those tuples, so the tests can check it on a synthetic
trace: busy time is the union of a device's op intervals inside the
window, a kernel's time is the sum of its events' durations, and each
idle gap of a device is put down to the host event that covers most of it.
"""
from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"


@dataclass(frozen=True)
class Event:
    start_ns: float
    end_ns: float
    name: str


@dataclass
class Trace:
    devices: dict          # device index -> [Event] of its XLA Ops line
    host: list             # [Event] of every host thread, by start
    window: tuple          # (start_ns, end_ns) of the bench.window span


def read(path: str) -> Trace:
    """Parse an `.xplane.pb` written by `jax.profiler`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices[int(m.group(1))] = [
                Event(e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            host.extend(Event(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for line in plane.lines for e in line.events)
    return from_events(devices, host)


def from_events(devices: dict, host: list) -> Trace:
    host = sorted(host, key=lambda e: e.start_ns)
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w = spans[-1]
    return Trace(devices={k: sorted(v, key=lambda e: e.start_ns)
                          for k, v in devices.items()},
                 host=[e for e in host if e.name != WINDOW_SPAN],
                 window=(w.start_ns, w.end_ns))


def window_s(tr: Trace) -> float:
    return (tr.window[1] - tr.window[0]) / 1e9


def _clip(events, lo, hi):
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            yield s, t


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [tuple(x) for x in out]


def busy_ns(tr: Trace, device: int, match=None) -> float:
    """Time inside the window in which some op (or, with `match`, some op
    whose name it accepts) ran on `device`; nested ops count once."""
    evs = tr.devices.get(device, [])
    if match is not None:
        evs = [e for e in evs if match(e.name)]
    return sum(t - s for s, t in union(_clip(evs, *tr.window)))


def busy_s(tr: Trace, devices) -> float:
    """Busy seconds averaged over `devices`."""
    return sum(busy_ns(tr, d) for d in devices) / len(devices) / 1e9


def op_name(event_name: str) -> str:
    """'%_chain_planes.2 = (...) custom-call(...)' -> '_chain_planes';
    a custom call other than a Pallas kernel keeps its target:
    'custom-call:TopK'."""
    m = re.match(r"%?([\w\-.]+?)(?:\.\d+)? = ", event_name)
    name = m.group(1) if m else event_name.split(" ")[0]
    t = re.search(r'custom_call_target="([^"]+)"', event_name)
    if t and t.group(1) != "tpu_custom_call":
        name = f"{name}:{t.group(1)}"
    return name


def is_pallas(event_name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in event_name


def kernel_ns(tr: Trace, devices, prefixes) -> float:
    """Summed device time, inside the window, of the Pallas kernels whose
    op name starts with one of `prefixes`, over `devices`."""
    def match(n):
        return is_pallas(n) and op_name(n).startswith(tuple(prefixes))
    return sum(busy_ns(tr, d, match) for d in devices)


def top_ops(tr: Trace, devices, n: int = 10) -> list:
    """[[op name, seconds]] of the ops that took most device time,
    summed over `devices` (ops nested in a loop op count in both)."""
    tot = collections.Counter()
    lo, hi = tr.window
    for d in devices:
        for e in tr.devices.get(d, []):
            dur = min(e.end_ns, hi) - max(e.start_ns, lo)
            if dur > 0:
                tot[op_name(e.name)] += dur
    return [[k, v / 1e9] for k, v in tot.most_common(n)]


def idle_gaps(tr: Trace, devices, n: int = 10) -> list:
    """[[host activity, seconds]]: each gap in which `devices` ran nothing,
    put down to the innermost host event that covers most of it, summed
    by name, longest first.  Gaps no host event covers are 'host idle'."""
    starts = [e.start_ns for e in tr.host]
    longest = max((e.end_ns - e.start_ns for e in tr.host), default=0)
    tot = collections.Counter()
    for d in devices:
        busy = union(_clip(tr.devices.get(d, []), *tr.window))
        edges = [tr.window[0]] + [x for iv in busy for x in iv] \
            + [tr.window[1]]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi <= lo:
                continue
            first = bisect.bisect_left(starts, lo - longest)
            best, best_key = "host idle", None
            for e in tr.host[first:bisect.bisect_right(starts, hi)]:
                ov = min(e.end_ns, hi) - max(e.start_ns, lo)
                if ov <= 0:
                    continue
                key = (ov, -(e.end_ns - e.start_ns))
                if best_key is None or key > best_key:
                    best, best_key = e.name, key
            tot[best] += hi - lo
    return [[k, v / 1e9 / len(devices)] for k, v in tot.most_common(n)]
