"""Task `extract`: `CvEngine.extract` serves SIFT descriptor sets.

The engine has one rung ("streaming") and one bucket, the frame's own
size, so no rung can hide a broken kernel.  Each served frame's
descriptor set is held to the plain reference's for the same frame.
"""
from __future__ import annotations

import numpy as np

from benchlib import compare, sift
from benchlib import reference as plain
from benchlib.sift import stencil_chains  # noqa: F401  (task interface)

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


def reference(cfg: dict, frames, head, dtype: str) -> list:
    """The plain reference's {"desc", "valid"} of each frame."""
    return plain.reference(cfg, frames, head, dtype=dtype)


def control_answer(ref: dict) -> dict:
    return {"desc": ref["desc"], "valid": ref["valid"]}


def misses(answers, refs: dict) -> list:
    """Where a served descriptor has no reference counterpart within
    DESC_ATOL: (pool frame, keypoint, its nearest reference distance),
    to tell an orientation bin crossing (a few hundredths) from another
    keypoint in the top-k (tenths)."""
    out, seen = [], set()
    for i, a in answers:
        if i in seen:
            continue
        seen.add(i)
        r = refs[i]
        got, want = a["desc"][a["valid"]], r["desc"][r["valid"]]
        near = np.array([np.abs(g[None] - want).max(axis=-1).min()
                         for g in got])
        for k in np.nonzero(near > DESC_ATOL)[0]:
            out.append([int(i), int(k), float(near[k])])
    return out


def readings(cfg: dict, answers: list, refs: dict, head) -> dict:
    """The reference's keypoint counts and every served descriptor that
    missed."""
    return dict(sift.keypoint_range(refs), misses=misses(answers, refs))
