"""A stand-in task that lives only in the tests: u8 C1 frames through a
one-stage erosion chain (`repro.kernels.erode`), held to a plain NumPy
erosion.  It is no cell of BENCHMARK.json.  It shows that a deployment
other than SIFT runs through the harness and `bench/readings.py` with
nothing but a task module, a configuration and a traffic mix of its own.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

import tinycells

PLAN = "erode"
CONFIG = {"name": "erode_c1", "task": "erode_task",
          "frame": {"height": tinycells.H, "width": tinycells.W,
                    "channels": 1, "dtype": "uint8"},
          "erode_radius": 1, "limits": {"px_wrong": 0}}
TRAFFIC = {"loop": "closed", "clients": 1, "batch": 2, "pool": 4}
WORKLOAD = {"name": "erode_c1.b2", "config": "erode_c1", "traffic": "b2",
            "chips": 1}


def cell(config=CONFIG):
    import sys
    from benchlib import spec
    return spec.Cell(WORKLOAD["name"], tinycells.ROOT, config=config,
                     traffic=TRAFFIC, workload=WORKLOAD,
                     task=sys.modules[__name__])


@dataclass
class Response:
    out: np.ndarray
    latency_s: float
    ok: bool = True
    plan: str = PLAN
    degraded: bool = False
    events: tuple = ()


class Engine:
    """One jitted erosion of a (B, H, W, 1) u8 batch."""

    def __init__(self, radius: int):
        import jax
        from repro.kernels.erode import erode
        self._fn = jax.jit(lambda x: erode(x, radius))

    def _batch(self, x):
        return self._fn(x)

    def run(self, frames) -> list:
        import jax.numpy as jnp
        t = time.perf_counter()
        out = np.asarray(self._batch(jnp.asarray(np.stack(frames))))
        lat = time.perf_counter() - t
        return [Response(out=o, latency_s=lat) for o in out]


def head(cfg: dict, seed: int):
    return None


def engine(cfg: dict, head_arrays, *, mesh=None, max_batch: int):
    return Engine(cfg["erode_radius"])


def submit(eng, frames):
    return eng.run(frames)


def answer(resp) -> dict:
    return {"out": resp.out}


def reference(cfg: dict, frames, head_arrays, dtype: str) -> list:
    """Erosion by the (2r+1)^2 square, edge replicated, in `dtype`."""
    import jax.numpy as jnp
    r = cfg["erode_radius"]
    outs = []
    for f in np.asarray(frames):
        x = np.pad(f.astype(jnp.dtype(dtype)), ((r, r), (r, r), (0, 0)),
                   mode="edge")
        h, w = f.shape[:2]
        out = x[:h, :w]
        for i in range(2 * r + 1):
            for j in range(2 * r + 1):
                out = np.minimum(out, x[i:i + h, j:j + w])
        outs.append({"out": out.astype(np.uint8)})
    return outs


def control_answer(ref: dict) -> dict:
    return {"out": ref["out"]}


def numbers(cfg: dict, answers: list, refs: dict) -> dict:
    """Most pixels of one served frame that differ from the reference."""
    return {"px_wrong": max(int((a["out"] != refs[k]["out"]).sum())
                            for k, a in answers)}


def stencil_chains(cfg: dict) -> list:
    f = cfg["frame"]
    c = f["channels"]
    return [(f["height"], f["width"], c, c, "uint8")]
