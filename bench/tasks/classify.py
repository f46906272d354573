"""Task `classify`: `CvEngine.classify` serves BoW + one-vs-rest SVM
labels, extraction as in task `extract` followed by the fused tail.

The dictionary and the SVM come from the seed, made on the device in one
call, not trained: SIFT-like unit words (non-negative, clamped at the
descriptor clamp, renormalised) and normal weights.  Each served label is
held to the plain reference's scores for the same frame.
"""
from __future__ import annotations

from benchlib import compare, frames

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
