"""Task `classify`: `CvEngine.classify` serves BoW + one-vs-rest SVM
labels, extraction as in task `extract` followed by the fused tail.

The dictionary and the SVM come from the seed, made on the device in one
call, not trained: SIFT-like unit words (non-negative, clamped at the
descriptor clamp, renormalised) and normal weights.  Each served label is
held to the plain reference's scores for the same frame.
"""
from __future__ import annotations

import numpy as np

from benchlib import compare, frames, sift
from benchlib import reference as plain
from benchlib.sift import stencil_chains  # noqa: F401  (task interface)

PLAN = "streaming"
HEAD_STREAM = 0x5EED     # key stream of the head, apart from the frames'


def head(cfg: dict, seed: int):
    """(centroids (K, D), w (C, K), b (C,)) f32 on the device."""
    import jax
    import jax.numpy as jnp
    h = cfg["head"]
    k, d, c = h["dict_size"], h["desc_dim"], h["n_classes"]
    clamp = cfg["descriptor"]["clamp"]

    @jax.jit
    def make(key):
        kc, kw, kb = jax.random.split(key, 3)
        words = jnp.abs(jax.random.normal(kc, (k, d), jnp.float32))
        words = words / jnp.linalg.norm(words, axis=1, keepdims=True)
        words = jnp.minimum(words, clamp)
        words = words / jnp.linalg.norm(words, axis=1, keepdims=True)
        w = jax.random.normal(kw, (c, k), jnp.float32)
        b = h["bias_scale"] * jax.random.normal(kb, (c,), jnp.float32)
        return words, w, b

    return make(jax.random.fold_in(frames.seed_key(seed), HEAD_STREAM))


def engine(cfg: dict, head_arrays, *, mesh=None, max_batch: int):
    from repro.cv.config import PipelineConfig
    from repro.cv.pipeline import BowSvmModel
    from repro.serve.cv_engine import CvEngine
    centroids, w, b = head_arrays
    model = BowSvmModel(centroids=centroids, svm={"w": w, "b": b},
                        n_classes=cfg["head"]["n_classes"])
    pc = PipelineConfig(max_kp=cfg["max_kp"], preprocess=True,
                        n_octaves=cfg["n_octaves"], classify_ladder=None)
    hw = (cfg["frame"]["height"], cfg["frame"]["width"])
    return CvEngine(model, config=pc, buckets=(hw,), ladder=(PLAN,),
                    mesh=mesh, max_batch=max_batch)


def submit(eng, frames_):
    return eng.classify(frames_)


def answer(resp) -> dict:
    return {"label": resp.pred}


def numbers(cfg: dict, answers: list, refs: dict) -> dict:
    """Widest gap, over the served frames, between the reference's best
    score and its score of the served label."""
    worst = 0.0
    for k, a in answers:
        worst = max(worst, compare.label_gap(refs[k]["scores"], a["label"]))
    return {"label_gap": worst}


def reference(cfg: dict, frames_, head_arrays, dtype: str) -> list:
    """The plain reference's {"desc", "valid", "scores"} of each frame."""
    return plain.reference(cfg, frames_, head_arrays, dtype=dtype)


def control_answer(ref: dict) -> dict:
    return {"label": int(np.argmax(ref["scores"]))}


def tail_answers(refs: dict, head_arrays, *, dtype: str,
                 keep_half: bool) -> list:
    """Labels from the reference's float32 descriptors through the
    reference tail alone, in `dtype`, or with the second half of each
    frame's keypoints left out of its histogram."""
    import jax.numpy as jnp
    ans = []
    for i, o in sorted(refs.items()):
        valid = np.asarray(o["valid"], bool)
        if keep_half:
            valid = valid & (np.cumsum(valid) <= valid.sum() // 2)
        s = plain.bow_scores(jnp.asarray(o["desc"]), jnp.asarray(valid),
                             *head_arrays, jnp.dtype(dtype))
        ans.append((i, {"label": int(np.argmax(np.asarray(s, np.float32)))}))
    return ans


def readings(cfg: dict, answers: list, refs: dict, head_arrays) -> dict:
    """The reference's keypoint counts, and two readings of the tail
    alone on the float32 reference's descriptors: the tail in bfloat16,
    and the tail with half of each frame's descriptors left out of its
    histogram."""
    return dict(
        sift.keypoint_range(refs),
        tail_bf16=numbers(cfg, tail_answers(refs, head_arrays,
                                            dtype="bfloat16",
                                            keep_half=False), refs),
        tail_half=numbers(cfg, tail_answers(refs, head_arrays,
                                            dtype="float32",
                                            keep_half=True), refs))
