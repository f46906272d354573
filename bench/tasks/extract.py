"""Task `extract`: `CvEngine.extract` serves SIFT descriptor sets.

The engine has one rung ("streaming") and one bucket, the frame's own
size, so no rung can hide a broken kernel.  Each served frame's
descriptor set is held to the plain reference's for the same frame.
"""
from __future__ import annotations

import numpy as np

from benchlib import compare

PLAN = "streaming"
# a descriptor element that differs by more than this has not matched
DESC_ATOL = 1e-3


def head(cfg: dict, seed: int):
    """Extraction serves no classifier."""
    return None


def engine(cfg: dict, head_arrays, *, mesh=None, max_batch: int):
    from repro.cv.config import PipelineConfig
    from repro.serve.cv_engine import CvEngine
    pc = PipelineConfig(max_kp=cfg["max_kp"], preprocess=True,
                        n_octaves=cfg["n_octaves"], classify_ladder=None)
    hw = (cfg["frame"]["height"], cfg["frame"]["width"])
    return CvEngine(None, config=pc, buckets=(hw,), ladder=(PLAN,),
                    mesh=mesh, max_batch=max_batch)


def submit(eng, frames):
    return eng.extract(frames)


def answer(resp) -> dict:
    """What a response says, kept until the window has closed: its valid
    descriptors, the only rows compared."""
    valid = np.asarray(resp.valid, bool)
    return {"desc": np.asarray(resp.desc)[valid],
            "valid": np.ones(int(valid.sum()), bool)}


def numbers(cfg: dict, answers: list, refs: dict) -> dict:
    """Worst served frame: `answers` holds (pool index, answer).  A frame
    served the same way twice is compared once."""
    worst, seen = 0.0, set()
    for k, a in answers:
        key = (k, a["desc"].tobytes())
        if key in seen:
            continue
        seen.add(key)
        r = refs[k]
        worst = max(worst, compare.unmatched_share(
            a["desc"], a["valid"], r["desc"], r["valid"], DESC_ATOL))
    return {"unmatched_kp": worst}
