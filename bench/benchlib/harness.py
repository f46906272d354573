"""One run of one cell: set-up, the measured window, the check of what
the window served, and the metrics.

`run()` does everything but print; `bench/run.py` is its command line.
The window is a closed loop with one client: the next submit leaves when
the previous one has returned.  Every answer served in the window is kept
and, once the window has closed and the device memory peak is read, held
to the plain reference of the cell's task for the same frame.
"""
from __future__ import annotations

import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

from benchlib import frames, stats
from benchlib import trace as trace_mod
from benchlib.peaks import peaks
from benchlib.spec import SpecError

WARM_SUBMITS = 2        # the first compiles (or loads), the second is steady


class NoChip(RuntimeError):
    """JAX found no accelerator of the cell's platform, or too few."""


class NoProgram(RuntimeError):
    """The checkout holds no program to measure."""


@dataclass
class Submit:
    start: float            # host clock, seconds
    end: float
    pool_idx: list          # pool frames sent, in order
    served_s: float         # the engine's own latency of the batch
    bad: int                # frames not served as the cell demands


@dataclass
class Context:
    """What a per-layer metric reader may read."""
    config: dict
    submits: list
    images: int
    devices: list           # device indices the cell uses
    device_kind: str
    task: object            # the cell's task module
    trace: object = None    # benchlib.trace.Trace of the window, when traced

    def peak(self, key: str) -> float:
        return peaks(self.device_kind)[key]


@dataclass
class Result:
    correct: bool
    attempted: int
    failed: int
    metrics: dict
    device: dict
    checks: dict
    breakdown: dict | None = None
    notes: list = field(default_factory=list)

    def line(self) -> dict:
        out = {"correct": self.correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metrics,
               "device": self.device}
        if self.breakdown is not None:
            out["breakdown"] = self.breakdown
        out["checks"] = self.checks
        return out


def use_program(root: str) -> None:
    """Put the checkout's `src/` first on the path."""
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise NoProgram(f"no program under {src}: run from the root of a "
                        "checkout")
    if src not in sys.path:
        sys.path.insert(0, src)


def devices_for(cell, platform: str):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"jax.devices()[0] is {devs[0].platform!r}, not "
                     f"{platform!r}")
    if len(devs) < cell.chips:
        raise NoChip(f"{len(devs)} device(s), the cell asks for {cell.chips}")
    return devs


def served_ok(responses, plan: str) -> int:
    """Frames of one submit that were not served by `plan` as asked."""
    return sum(1 for r in responses
               if not r.ok or r.plan != plan or r.degraded or r.events)


class Schedule:
    """Pool indices of each submit: the pool in a seeded order, cycled.
    Every seed sends the same sizes; only the frames differ."""

    def __init__(self, seed: int, pool: int, batch: int):
        self.perm = np.random.default_rng(seed).permutation(pool).tolist()
        self.batch = batch

    def __getitem__(self, k: int) -> list:
        n = len(self.perm)
        return [self.perm[(k * self.batch + j) % n]
                for j in range(self.batch)]


class Setup:
    """Everything a window needs, built from the seed; each step timed."""

    def __init__(self, cell, seed: int, *, platform: str = "tpu",
                 t_start: float | None = None):
        t_start = time.perf_counter() if t_start is None else t_start
        self.times = {}
        devs = devices_for(cell, platform)
        from repro.core import jaxcache
        self.cache_dir = jaxcache.enable()
        self.cell, self.seed = cell, seed
        self.all_devices = devs
        self.devices = devs[:cell.chips]
        t = time.perf_counter()
        self.times["device_init_s"] = t - t_start
        cfg, tr = cell.config, cell.traffic
        f = cfg["frame"]
        if f["dtype"] != "uint8":
            raise SpecError(f"{cfg['name']}: frames of {f['dtype']!r}; the "
                            "frame pool makes uint8 only")
        self.pool = frames.pool(seed, tr["pool"], f["height"], f["width"],
                                f["channels"])
        self.head = cell.task.head(cfg, seed)
        t2 = time.perf_counter()
        self.times["model_build_s"] = t2 - t
        mesh = None
        if cell.chips > 1:
            from repro.launch.mesh import make_cv_mesh
            mesh = make_cv_mesh(cell.chips)
        self.engine = cell.task.engine(cfg, self.head, mesh=mesh,
                                       max_batch=tr["batch"])
        self.schedule = Schedule(seed, tr["pool"], tr["batch"])
        for _ in range(WARM_SUBMITS):
            res = self.submit(self.schedule[0])
            bad = served_ok(res, cell.task.PLAN)
            if bad:
                raise RuntimeError(f"warm-up: {bad} frame(s) not served by "
                                   f"{cell.task.PLAN!r}: {res[0].error}")
        t3 = time.perf_counter()
        self.times["engine_warm_s"] = t3 - t2
        self.setup_s = t3 - t_start

    def submit(self, idx):
        return self.cell.task.submit(self.engine, [self.pool[i] for i in idx])


def window(st: Setup, seconds: float, *, trace_dir: str | None = None):
    """The closed loop for `seconds`: (submits, answers, compile counts)."""
    import jax
    from repro.core import faultinject, jaxcache
    cell = st.cell
    faultinject.clear_degradation_log()
    c0 = jaxcache.counts()
    subs, answers = [], []
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
    span.__enter__()
    try:
        t0 = time.perf_counter()
        k = 0
        while True:
            idx = st.schedule[k]
            logged = len(faultinject.degradation_log())
            s = time.perf_counter()
            res = st.submit(idx)
            e = time.perf_counter()
            bad = served_ok(res, cell.task.PLAN)
            if len(faultinject.degradation_log()) > logged:
                bad = len(idx)
            subs.append(Submit(s, e, idx, res[0].latency_s, bad))
            answers.extend((i, cell.task.answer(r)) for i, r in zip(idx, res))
            k += 1
            if e - t0 >= seconds:
                break
    finally:
        span.__exit__(None, None, None)
        if trace_dir is not None:
            jax.profiler.stop_trace()
    c1 = jaxcache.counts()
    counts = {k: c1[k] - c0[k] for k in c1}
    return subs, answers, counts


def memory_peak(devs) -> int | None:
    """Peak bytes in use on the fullest device (None where the backend
    keeps no such count)."""
    got = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    got = [p for p in got if p is not None]
    return max(got) if got else None


def references(cell, pool, head, served, *, dtype: str = "float32") -> dict:
    """The task's plain reference, computed in `dtype`, of each pool frame
    in `served`, by pool index."""
    return dict(zip(served, cell.task.reference(cell.config, pool[served],
                                                head, dtype)))


def judge(cell, answers, refs) -> dict:
    """The cell's numbers beside their limits: every answer against the
    reference of its pool frame."""
    nums = cell.task.numbers(cell.config, answers, refs)
    limits = cell.config["limits"]
    return {k: {"value": v, "limit": limits[k]} for k, v in nums.items()}


def find_xplane(d: str) -> str:
    for root, _, files in os.walk(d):
        for f in files:
            if f.endswith(".xplane.pb"):
                return os.path.join(root, f)
    raise FileNotFoundError(f"no .xplane.pb under {d}")


def run(cell, *, seed: int, seconds: float, trace: bool,
        platform: str = "tpu", t_start: float | None = None,
        log=print) -> Result:
    st = Setup(cell, seed, platform=platform, t_start=t_start)
    log(f"setup: {st.setup_s:.3f}s = " + ", ".join(
        f"{k} {v:.3f}s" for k, v in st.times.items())
        + f"; compile cache {st.cache_dir}")
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        subs, answers, counts = window(st, seconds, trace_dir=tdir)
        tr = trace_mod.read(find_xplane(tdir)) if trace else None
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    lat = [s.end - s.start for s in subs]
    images = sum(len(s.pool_idx) for s in subs)
    failed = sum(s.bad for s in subs)
    log(f"window: {len(subs)} submits, {images} frames, {failed} failed; "
        f"compile requests {counts['requests']}, cache hits {counts['hits']}, "
        f"compiled {counts['compiled']}; submit latency p50 "
        f"{stats.percentile(lat, 50) * 1e3:.3f}ms p95 "
        f"{stats.percentile(lat, 95) * 1e3:.3f}ms max {max(lat) * 1e3:.3f}ms")
    dev0 = st.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(st.all_devices),
              "memory_peak_bytes": memory_peak(st.devices)}
    ctx = Context(config=cell.config, submits=subs, images=images,
                  devices=[d.id for d in st.devices],
                  device_kind=dev0.device_kind, task=cell.task, trace=tr)
    metrics, breakdown, notes = {}, None, []
    if trace:
        device["busy_s"] = trace_mod.busy_s(tr, ctx.devices)
        device["window_s"] = trace_mod.window_s(tr)
        breakdown = {"device_ops": trace_mod.top_ops(tr, ctx.devices),
                     "idle_gaps": trace_mod.idle_gaps(tr, ctx.devices)}
        for m, reader in cell.per_layer():
            v = reader.read(ctx)
            if v is None:
                notes.append(f"{m['name']} found nothing to read")
                continue
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"images_per_s": lambda: stats.images_per_s(
                   [(s.start, s.end, len(s.pool_idx)) for s in subs]),
               "p95_latency_ms": lambda: stats.percentile(lat, 95) * 1e3,
               "setup_s": lambda: st.setup_s}
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": e2e[m["name"]](), "unit": m["unit"]}
    pool, head = st.pool, st.head
    del st                  # the program's state goes before the reference
    served = sorted({i for i, _ in answers})
    checks = judge(cell, answers, references(cell, pool, head, served))
    checks["failed_frames"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return Result(correct=correct, attempted=images, failed=failed,
                  metrics=metrics, device=device, checks=checks,
                  breakdown=breakdown, notes=notes)
