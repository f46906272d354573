"""What `BENCHMARK.json` names, and the files the harness finds by name:
`bench/configs/<config>.json`, `bench/traffic/<traffic>.json`,
`bench/tasks/<task>.py` (named by the configuration's `task` key) and
`bench/metrics/<metric>.py`.  Adding a cell, a configuration, a traffic
mix or a metric adds files and entries; nothing here is edited.

A task module is the one place that knows what its deployment serves and
how an answer is judged.  The harness, `bench/readings.py` and the
per-layer readers call it and assume no task.  It defines:

- `PLAN`: the plan (rung) every response must report;
- `head(cfg, seed)`: anything beside the frames the engine serves from,
  such as a classifier, made on the device from the seed (or None);
- `engine(cfg, head, *, mesh, max_batch)`: the program under test, built
  once in set-up;
- `submit(engine, frames)`: one timed call, a list of frames in, one
  response per frame out (each with `ok`, `plan`, `degraded`, `events`
  and `latency_s`);
- `answer(response)`: what a response says, kept until the window has
  closed;
- `reference(cfg, frames, head, dtype)`: the plain reference, computed in
  `dtype` ("float32", or "bfloat16" for the control), one dict of host
  arrays per frame of the (N, H, W, C) u8 `frames`.  It imports nothing
  of the program under test and takes none of its tables;
- `control_answer(ref)`: an answer built from one reference output, so
  that the bfloat16 reference can stand in the program's place;
- `numbers(cfg, answers, refs)`: the numbers that decide `correct`, by
  name, each held to `cfg["limits"][name]`; `answers` holds (pool index,
  answer) pairs and `refs` the reference by pool index.

Optional:

- `stencil_chains(cfg)`: [(h, w, planes_in, planes_out, dtype), ...], the
  stencil chains one frame runs, for `benchlib.stencil_bytes`; a task
  whose cells list `stencil_roofline` defines it;
- `readings(cfg, answers, refs, head)`: a dict of further readings for
  `bench/readings.py`.  A dict-valued reading holds the task's numbers
  for another stand-in of the program (an upper reading); a reading
  named `*_max` is summarised by its largest value over the seeds.

The frames are u8 with the configuration's `frame.channels`.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(ValueError):
    """A name that BENCHMARK.json or a file under bench/ does not hold."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def _file(kind: str, name: str, ext: str, bench: str = BENCH) -> str:
    path = os.path.join(bench, kind, name + ext)
    if not os.path.isfile(path):
        raise SpecError(f"no file bench/{kind}/{name}{ext} for {name!r}")
    return path


def load_module(path: str, name: str):
    """Import a file by path (names may hold dots, as metric names do)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of `workloads` with its configuration, traffic, task and
    the per-layer metrics that read something in it."""

    def __init__(self, name: str, root: str = ROOT, *, config=None,
                 traffic=None, workload=None, task=None):
        """`config`/`traffic`/`workload`/`task` replace what the files hold
        (tests run a cell at a size the CPU can hold, or a task of their
        own)."""
        bench = os.path.join(root, "bench")
        self.spec = benchmark(root)
        self.workload = workload or _by_name(self.spec["workloads"], name,
                                             "workload")
        self.name = name
        self.chips = int(self.workload["chips"])
        if config is None:
            cfg_entry = _by_name(self.spec["configs"],
                                 self.workload["config"], "configuration")
            config = load_json(os.path.join(root, cfg_entry["file"]))
        self.config = config
        self.traffic = traffic or load_json(_file(
            "traffic", self.workload["traffic"], ".json", bench))
        self.task = task or load_module(
            _file("tasks", config["task"], ".py", bench),
            f"bench_task_{config['task']}")
        self._bench = bench

    def _listed(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self) -> list:
        return [m for m in self.spec["end_to_end"] if self._listed(m)]

    def per_layer(self) -> list:
        """(entry, reader module) of every per-layer metric that lists
        this cell under `workloads`, which every per-layer entry has."""
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" not in m:
                raise SpecError(f"per-layer metric {m['name']!r} lists no "
                                "workloads")
            if self.name in m["workloads"]:
                out.append((m, load_module(
                    _file("metrics", m["name"], ".py", self._bench),
                    "bench_metric_" + m["name"].replace(".", "_"))))
        return out
