"""Cells of BENCHMARK.json cut to a size the CPU can run in a test:
48x128 frames with room for 256 keypoints (such a frame has some tens),
2 frames per submit (8 on four virtual devices), the Pallas kernels
interpreted."""
from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

H, W, MAX_KP = 48, 128, 256


def cell(name: str, *, batch: int = 2, pool: int = 4):
    from benchlib import spec
    c = spec.Cell(name, ROOT)
    cfg = dict(c.config, frame=dict(c.config["frame"], height=H, width=W),
               max_kp=MAX_KP)
    return spec.Cell(name, ROOT, config=cfg,
                     traffic=dict(c.traffic, batch=batch, pool=pool))
