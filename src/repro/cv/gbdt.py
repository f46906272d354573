"""Gradient-boosted oblivious decision trees over BoW histograms.

The second classifier head of the paper's §4.5 pipeline (the first is
the one-vs-rest SVM): a CatBoost-style *oblivious* ensemble per
arXiv:2405.11062 — every node at depth l of a tree shares one
(feature, threshold) split, so a tree of depth d is d comparisons and
its leaf index is the d-bit comparison mask (level l contributes bit
2^l, matching `kernels.gbdt` / `kernels.ref.gbdt_leaf_ref`).

Training is deterministic multi-output residual boosting (squared-error
on one-hot class targets, a jit-friendly stand-in for softmax-gradient
boosting): each tree greedily picks, level by level, the single
(feature, quantile-threshold) split that maximizes the oblivious
variance gain over the *whole* current partition, then fits shrunken
mean-residual leaf values.  Prediction runs through the fused Pallas
kernel (`kernels.gbdt.gbdt_score`) or the staged oracle
(`kernels.ref.gbdt_scores_ref`) behind `cv.classify.ClassifyPlan`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref

Array = jax.Array


@dataclass
class GbdtModel:
    """Oblivious-tree ensemble: feat/thr (T, depth), leaf (T, 2^depth, C),
    base (C,) — the little-endian-in-level leaf layout of kernels.gbdt."""
    feat: Array
    thr: Array
    leaf: Array
    base: Array
    n_classes: int


jax.tree_util.register_dataclass(
    GbdtModel, data_fields=["feat", "thr", "leaf", "base"],
    meta_fields=["n_classes"])


def _level_split(x: Array, r: Array, pid: Array, n_leaves: int,
                 thresholds: Array):
    """Best oblivious split for one level: maximize sum over children of
    |sum residuals|^2 / count.  x (N, F), r (N, C), pid (N,) current
    partition, thresholds (F, Q) candidate values per feature.
    Returns (feature, threshold, bits (N,))."""
    N, F = x.shape
    Q = thresholds.shape[1]
    # bits for every candidate: (N, F, Q)
    bits = x[:, :, None] > thresholds[None, :, :]
    poh = jax.nn.one_hot(pid, n_leaves, dtype=jnp.float32)     # (N, P)
    bf = bits.reshape(N, F * Q).astype(jnp.float32)
    # right-child stats per (candidate, parent): sums (F*Q, P, C), counts
    s_all = jnp.einsum("np,nc->pc", poh, r)                    # (P, C)
    c_all = jnp.sum(poh, axis=0)                               # (P,)
    s_r = jnp.einsum("nq,np,nc->qpc", bf, poh, r)              # (FQ, P, C)
    c_r = jnp.einsum("nq,np->qp", bf, poh)                     # (FQ, P)
    s_l = s_all[None] - s_r
    c_l = c_all[None] - c_r

    def score(s, c):
        return jnp.sum(jnp.sum(s * s, axis=-1)
                       / jnp.maximum(c, 1e-6), axis=-1)        # (FQ,)

    gain = score(s_r, c_r) + score(s_l, c_l)
    best = jnp.argmax(gain)
    f, q = best // Q, best % Q
    return f, thresholds[f, q], bits.reshape(N, F * Q)[:, best]


def gbdt_train(x: Array, y: Array, *, n_classes: int, n_trees: int = 16,
               depth: int = 3, lr: float = 0.5, n_bins: int = 8) -> GbdtModel:
    """Fit an oblivious GBDT on features x (N, F), labels y (N,) int."""
    x = jnp.asarray(x, jnp.float32)
    N, F = x.shape
    L = 2 ** depth
    yoh = jax.nn.one_hot(y, n_classes, dtype=jnp.float32)
    base = jnp.mean(yoh, axis=0)
    pred = jnp.broadcast_to(base, (N, n_classes))
    # per-feature candidate thresholds: interior quantiles of the data
    qs = jnp.linspace(0.0, 1.0, n_bins + 2)[1:-1]
    thresholds = jnp.quantile(x, qs, axis=0).T                 # (F, Q)

    feats, thrs, leaves = [], [], []
    for _ in range(n_trees):
        r = yoh - pred
        pid = jnp.zeros((N,), jnp.int32)
        tf, tt = [], []
        for lvl in range(depth):
            f, t, bits = _level_split(x, r, pid, 2 ** lvl, thresholds)
            tf.append(f)
            tt.append(t)
            pid = pid + bits.astype(jnp.int32) * (2 ** lvl)
        poh = jax.nn.one_hot(pid, L, dtype=jnp.float32)        # (N, L)
        cnt = jnp.sum(poh, axis=0)                             # (L,)
        mean_r = (poh.T @ r) / jnp.maximum(cnt[:, None], 1e-6)
        leaf = lr * jnp.where(cnt[:, None] > 0, mean_r, 0.0)   # (L, C)
        pred = pred + poh @ leaf
        feats.append(jnp.stack(tf))
        thrs.append(jnp.stack(tt))
        leaves.append(leaf)

    return GbdtModel(feat=jnp.stack(feats).astype(jnp.int32),
                     thr=jnp.stack(thrs).astype(jnp.float32),
                     leaf=jnp.stack(leaves).astype(jnp.float32),
                     base=base.astype(jnp.float32),
                     n_classes=n_classes)


def gbdt_predict_ref(model: GbdtModel, x: Array) -> Array:
    """Staged-oracle class prediction (the ClassifyPlan "ref" rung)."""
    s = kref.gbdt_scores_ref(jnp.asarray(x, jnp.float32), model.feat,
                             model.thr, model.leaf, model.base)
    return jnp.argmax(s, axis=1).astype(jnp.int32)
