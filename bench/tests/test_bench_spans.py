"""The serving-path readers on the program's own spans: a synthetic
trace for the arithmetic, and a traced run of the four-chip cell at a tiny
size on four virtual CPU devices for the spans the program emits."""
import os
import re
import subprocess
import sys

import pytest

import tinycells
from benchlib import harness, spans, spec, trace

E = trace.Event
NEW = ("front_end_ms.batch", "dispatch_ms.batch", "transfer_ms.batch",
       "compile_requests_per_submit", "fanout_ms")
SINGLE = ("front_end_ms.single", "dispatch_ms.single", "transfer_ms.single")


def _tr(host, devices=None):
    """Window 0..1000 us; times in us."""
    evs = [E(s * 1e3, t * 1e3, n) for n, s, t in host]
    return trace.from_events(devices or {0: []},
                             [E(0, 1000e3, trace.WINDOW_SPAN)] + evs)


# two submits; the second goes through the fan-out, whose transfers,
# dispatch and wait leave it 20 us of self time
SUBMITS = [
    ("serve.submit", 10, 110), ("serve.admit", 12, 20),
    ("serve.stack", 20, 25), ("serve.h2d", 25, 40),
    ("serve.dispatch", 40, 70), ("jaxcache.request", 45, 45),
    ("serve.wait", 70, 90), ("serve.d2h", 90, 100),
    ("serve.assemble", 100, 108),
    ("serve.submit", 200, 400), ("serve.admit", 201, 205),
    ("serve.stack", 205, 215), ("serve.fanout", 215, 380),
    ("serve.h2d", 220, 240), ("serve.dispatch", 240, 250),
    ("serve.wait", 250, 330), ("serve.d2h", 340, 370),
    ("serve.assemble", 380, 395),
    ("PjitFunction(scan)", 41, 69),
    ("jaxcache.request", 150, 150),          # between submits: not counted
    ("serve.submit", 990, 1100),             # leaves the window: not counted
    ("serve.admit", 991, 995),
]


def _ctx(tr, cell="sift1080p.b16"):
    c = spec.Cell(cell, tinycells.ROOT)
    return harness.Context(config=c.config, submits=[], images=2,
                           devices=[0], device_kind="TPU v5 lite",
                           task=c.task, trace=tr)


def _readers(cell):
    return {m["name"]: r for m, r in spec.Cell(cell, tinycells.ROOT).per_layer()}


def test_sums_per_submit():
    tr = _tr(SUBMITS)
    assert [s.start_ns for s in spans.submits(tr)] == [10e3, 200e3]
    # (8 + 5 + 8) and (4 + 10 + 15) us
    assert spans.mean_ms(tr, ("serve.admit", "serve.stack",
                              "serve.assemble")) == pytest.approx(25e-3)
    assert spans.mean_ms(tr, ("serve.h2d", "serve.d2h")) == \
        pytest.approx((25 + 50) / 2 * 1e-3)
    assert spans.mean_ms(tr, ("serve.dispatch",)) == pytest.approx(20e-3)


def test_self_time_leaves_out_nested_children():
    # fan-out 215-380 holds 220-330 and 340-370: 165 - 140 us
    tr = _tr(SUBMITS)
    assert spans.self_ms(tr, "serve.fanout") == pytest.approx(25e-3 / 2)
    # overlapping children count once
    tr = _tr([("serve.submit", 0, 100), ("serve.fanout", 0, 100),
              ("serve.h2d", 10, 50), ("serve.dispatch", 30, 60)])
    assert spans.self_ms(tr, "serve.fanout") == pytest.approx(50e-3)


def test_marks_counted_per_submit():
    tr = _tr(SUBMITS)
    assert spans.compile_requests_per_submit(tr) == pytest.approx(0.5)
    assert spans.compile_requests_per_submit(_tr(SUBMITS + [
        ("jaxcache.request", 300, 300), ("jaxcache.request", 301, 301)])) \
        == pytest.approx(1.5)


def test_submits_without_marks_read_zero():
    tr = _tr([e for e in SUBMITS if e[0] != "jaxcache.request"])
    got = _readers("sift1080p.b64x4")
    assert got["compile_requests_per_submit"].read(_ctx(tr)) == 0
    assert spans.self_ms(_tr(SUBMITS[:9]), "serve.fanout") == 0


@pytest.mark.parametrize("host", [[], [("PjitFunction(scan)", 0, 50)]])
def test_no_submit_span_reads_none(host):
    ctx = _ctx(_tr(host))
    for cell, names in (("sift1080p.b64x4", NEW), ("sift1080p.b1", SINGLE)):
        readers = _readers(cell)
        for name in names:
            assert readers[name].read(ctx) is None, name


def test_readers_on_submits():
    ctx = _ctx(_tr(SUBMITS))
    want = {"front_end_ms": 25e-3, "dispatch_ms": 20e-3,
            "transfer_ms": 37.5e-3, "compile_requests_per_submit": 0.5,
            "fanout_ms": 12.5e-3}
    for cell, names in (("sift1080p.b64x4", NEW), ("sift1080p.b1", SINGLE)):
        readers = _readers(cell)
        for name in names:
            assert readers[name].read(ctx) == pytest.approx(
                want[name.split(".")[0]]), name


def test_idle_gap_under_stack_goes_to_stack():
    # device busy 0-20 and 50-100; the gap 20-50 lies inside the submit
    # but only serve.stack covers it
    dev = {0: [E(0, 20e3, "%fusion = f32[2]"), E(50e3, 100e3, "%fusion")]}
    tr = trace.from_events(dev, [
        E(0, 100e3, trace.WINDOW_SPAN), E(0, 100e3, "serve.submit"),
        E(18e3, 52e3, "serve.stack")])
    gaps = dict(trace.idle_gaps(tr, [0]))
    assert gaps == pytest.approx({"serve.stack": 30e-6})


TRACED = r"""
import sys
sys.path.insert(0, {tests!r})
import tinycells
from benchlib import harness
name = sys.argv[1]
four = name.endswith("x4")
cell = tinycells.cell(name, batch=8 if four else 2, pool=8)
r = harness.run(cell, seed={seed}, seconds=0.5, trace=True,
                platform="cpu", log=print)
print("CORRECT", r.correct)
for k, v in sorted(r.metrics.items()):
    print("METRIC", k, v["value"])
"""


@pytest.mark.parametrize("name,devices", [("sift1080p.b64x4", 4),
                                          ("sift1080p.b16", 1)])
def test_traced_run_reads_every_new_metric(name, devices):
    """A traced tiny run on CPU devices: every new metric of the cell
    reads a number, and the compile requests per submit are the
    harness's own count of the window's requests over its submits (none
    on the collective path, whose program is jitted and cached)."""
    code = TRACED.format(tests=os.path.dirname(__file__), seed=2 ** 31 + 91)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    p = subprocess.run([sys.executable, "-c", code, name], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "CORRECT True" in p.stdout, p.stdout[-2000:]
    got = {ln.split()[1]: float(ln.split()[2])
           for ln in p.stdout.splitlines() if ln.startswith("METRIC")}
    listed = {m["name"]
              for m, _ in spec.Cell(name, tinycells.ROOT).per_layer()}
    assert set(NEW) & listed <= set(got), got
    win = re.search(r"window: (\d+) submits.*compile requests (\d+)",
                    p.stdout)
    n_sub, n_req = int(win.group(1)), int(win.group(2))
    assert got["compile_requests_per_submit"] == pytest.approx(n_req / n_sub)
    if devices == 4:
        assert n_req == 0
        assert got["fanout_ms"] > 0 and got["transfer_ms.batch"] > 0
