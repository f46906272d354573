"""Least HBM traffic of the stencil chains of one served frame.

Each chain reads its input planes once and writes its output planes once;
what it keeps on chip in between costs nothing here.  The cell's task
lists its chains from the configuration's shapes and dtypes alone
(`stencil_chains`), so the count is the same whatever implements them.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "uint8": 1}


def chain_bytes(h: int, w: int, planes_in: int, planes_out: int,
                dtype: str) -> int:
    return (planes_in + planes_out) * h * w * DTYPE_BYTES[dtype]


def frame_bytes(cfg: dict, task) -> int:
    """The bytes of every chain that `task` runs on one frame of `cfg`."""
    return sum(chain_bytes(*c) for c in task.stencil_chains(cfg))
