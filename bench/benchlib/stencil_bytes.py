"""Least HBM traffic of the stencil chains of one served frame.

Each chain reads its input planes once and writes its output planes once;
what it keeps on chip in between costs nothing here.  The count follows
from the configuration's shapes and dtypes alone, so it is the same
whatever implements the chains.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "uint8": 1}


def chain_bytes(h: int, w: int, planes_in: int, planes_out: int,
                dtype: str) -> int:
    return (planes_in + planes_out) * h * w * DTYPE_BYTES[dtype]


def frame_bytes(cfg: dict) -> int:
    """Preprocess chain (C planes in, C out) plus one SIFT octave (the
    gray plane in, one plane per scale out), at the configured plane
    dtype.  More than one octave is not counted yet."""
    if cfg["n_octaves"] != 1:
        raise NotImplementedError("stencil bytes of a multi-octave pyramid")
    f = cfg["frame"]
    h, w, c = f["height"], f["width"], f["channels"]
    dt = cfg["preprocess"]["plane_dtype"]
    pre = chain_bytes(h, w, c, c, dt)
    octave = chain_bytes(h, w, 1, len(cfg["octave_taps"]), dt)
    return pre + octave
