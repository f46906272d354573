"""Pallas TPU kernels: the BoW classifier tail (quantize -> histogram -> score).

The BoW feature-generation hot loop (paper §4.5) is "for every SIFT
descriptor, find the nearest dictionary centroid". On TPU this is an
MXU problem: d2(n, k) = |d_n|^2 - 2 d_n.c_k + |c_k|^2, i.e. a (N,128) x
(128,K) matmul. `bow_assign` fuses the matmul with a *running argmin*
across centroid blocks (flash-attention-style streaming state in VMEM
scratch), so the (N, K) distance matrix is never materialized in HBM — a
beyond-paper fusion recorded in EXPERIMENTS.md §Perf.

`bow_quantize_hist` goes one step further for the classify path: the
assignment indices themselves never reach HBM either.  One kernel walks
descriptor blocks x centroid blocks with the codebook VMEM-resident,
finishes each descriptor block's running argmin, and segment-sums the
block's valid-weights straight into a per-image histogram accumulated in
the revisited output block — the whole quantize->histogram stage is one
launch per batch.  `linear_score` is the one-vs-rest SVM decision matmul
(scores = h @ W^T + b) as a single launch with the class weights
VMEM-resident.

Arithmetic contract (the `ClassifyPlan` oracle relies on it): distances
are computed as  s = -2 d.c + |c|^2  (|d|^2 is argmin-invariant and
dropped), exactly mirroring `kernels.ref.bow_hist_ref` — histogram
counts are order-independent sums of {0, 1} weights, so fused histograms
are bit-identical to the staged oracle whenever the per-element dot
products agree (same contraction dim, no D padding).

lmul scales the descriptor-block rows (8 f32 sublanes x lmul).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vector import VectorConfig

Array = jax.Array

# f32 contractions at f32 precision on the MXU (the TPU default for f32
# is one bf16 pass), matching the staged oracles, which ask for the same
_HIGHEST = jax.lax.Precision.HIGHEST


def _pad_codebook(centroids: Array, bk: int):
    """Pad (K, D) centroids to a bk multiple; pad rows masked with +inf
    |c|^2 so the running argmin can never select them."""
    K = centroids.shape[0]
    k_pad = (-K) % bk
    c = jnp.pad(centroids.astype(jnp.float32), ((0, k_pad), (0, 0)))
    c2 = jnp.sum(c * c, axis=1)
    c2 = jnp.where(jnp.arange(c.shape[0]) < K, c2, jnp.inf)
    return c, c2[None, :]


def _running_argmin(d, c_ref, c2_ref, minv, mini, kb, bk):
    """Fold one centroid block into the running (min, argmin) scratch:
    s = -2 d.c + |c|^2 (|d|^2 is constant per row: argmin-invariant).
    Every per-row vector is a (bn, 1) column, a layout Mosaic tiles."""
    s = -2.0 * jax.lax.dot_general(d, c_ref[...], (((1,), (1,)), ((), ())),
                                   precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
    s = s + c2_ref[...]                                # (1, bk) row
    bmin = jnp.min(s, axis=1, keepdims=True)
    barg = jnp.argmin(s, axis=1, keepdims=True).astype(jnp.int32) + kb * bk
    better = bmin < minv[...]
    mini[...] = jnp.where(better, barg, mini[...])
    minv[...] = jnp.where(better, bmin, minv[...])


def _bow_kernel(d_ref, c_ref, c2_ref, idx_ref, val_ref, minv, mini, *, bk):
    kb = pl.program_id(1)
    nk = pl.num_programs(1)

    # +inf init (not a large-finite sentinel): the first real centroid
    # block always wins the compare, even for all-padding descriptor
    # blocks or pathological descriptor magnitudes whose true distance
    # exceeds any finite sentinel (the empty-descriptor-block edge).
    @pl.when(kb == 0)
    def _init():
        minv[...] = jnp.full(minv.shape, jnp.inf, jnp.float32)
        mini[...] = jnp.zeros(mini.shape, jnp.int32)

    _running_argmin(d_ref[...], c_ref, c2_ref, minv, mini, kb, bk)

    @pl.when(kb == nk - 1)
    def _done():
        idx_ref[...] = mini[...]
        val_ref[...] = minv[...]


@functools.partial(jax.jit, static_argnames=("vc",))
def bow_assign(desc: Array, centroids: Array, *, vc: VectorConfig = VectorConfig()):
    """desc (N, D) or batched (B, N, D), centroids (K, D) f32
    -> (idx i32, d2 f32) with the input's leading shape.

    d2 is the true squared distance (|d|^2 added back outside the kernel).
    The batched form flattens image rows into one blocked grid — the
    codebook stays VMEM-resident across every (row-block, centroid-block)
    step, descriptors stream through in (32*lmul)-row blocks.
    """
    if desc.ndim == 3:                     # blocked batched form
        B, N, D = desc.shape
        idx, d2 = bow_assign(desc.reshape(B * N, D), centroids, vc=vc)
        return idx.reshape(B, N), d2.reshape(B, N)
    N, D = desc.shape
    if N == 0:                             # empty descriptor set: no launch
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32))
    bn = vc.rows(jnp.float32) * 4          # MXU-friendly: 32*lmul rows
    bk = 128
    n_pad = (-N) % bn
    d = jnp.pad(desc.astype(jnp.float32), ((0, n_pad), (0, 0)))
    c, c2 = _pad_codebook(centroids, bk)

    idx, val = pl.pallas_call(
        functools.partial(_bow_kernel, bk=bk),
        grid=(d.shape[0] // bn, c.shape[0] // bk),
        in_specs=[
            pl.BlockSpec((bn, D), lambda n, k: (n, 0)),
            pl.BlockSpec((bk, D), lambda n, k: (k, 0)),
            pl.BlockSpec((1, bk), lambda n, k: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda n, k: (n, 0)),
            pl.BlockSpec((bn, 1), lambda n, k: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((d.shape[0], 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.int32),
        ],
        interpret=vc.run_interpret,
    )(d, c, c2)
    d2 = val[:N, 0] + jnp.sum(desc.astype(jnp.float32) ** 2, axis=1)
    return idx[:N, 0], d2


def _hist_kernel(d_ref, w_ref, c_ref, c2_ref, h_ref, minv, mini, *, bk, kp):
    nb = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    # the output block is revisited for every (n, k) step of this image:
    # zero it once, accumulate at each descriptor block's final k step
    @pl.when(jnp.logical_and(nb == 0, kb == 0))
    def _zero():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(kb == 0)
    def _init():
        minv[...] = jnp.full(minv.shape, jnp.inf, jnp.float32)
        mini[...] = jnp.zeros(mini.shape, jnp.int32)

    _running_argmin(d_ref[0], c_ref, c2_ref, minv, mini, kb, bk)

    @pl.when(kb == nk - 1)
    def _accumulate():
        # segment-sum of the block's valid weights by winning centroid:
        # one-hot(assignment) scaled by weight, reduced over rows — the
        # assignment indices stay in VMEM scratch, never reaching HBM
        w = w_ref[0]                                   # (bn, 1) f32
        bn = w.shape[0]
        oh = (jax.lax.broadcasted_iota(jnp.int32, (bn, kp), 1)
              == mini[...]).astype(jnp.float32)
        h_ref[...] += jnp.sum(oh * w, axis=0, keepdims=True)[None]


@functools.partial(jax.jit, static_argnames=("vc", "normalize"))
def bow_quantize_hist(descs: Array, valids: Array, centroids: Array, *,
                      vc: VectorConfig = VectorConfig(),
                      normalize: bool = True) -> Array:
    """Fused quantize->histogram: descs (B, N, D), valids (B, N) ->
    normalized word histograms (B, K) in ONE launch.

    Grid (B, N/bn, K/bk): per image, descriptor blocks stream against the
    VMEM-resident codebook with a running argmin; each block's final
    centroid step segment-sums its valid-weights into the image's
    histogram (accumulated in the revisited output block).  Neither the
    (N, K) distance matrix nor the (B, N) index array is materialized.
    Pad descriptor rows ride along with weight 0.
    """
    B, N, D = descs.shape
    K = centroids.shape[0]
    w = valids.astype(jnp.float32)
    if N == 0:
        h = jnp.zeros((B, K), jnp.float32)
        return h / jnp.maximum(jnp.sum(h, axis=1, keepdims=True), 1e-6) \
            if normalize else h
    # descriptor block: 32*lmul rows, shrunk (sublane-aligned) for small
    # per-image keypoint budgets so a 32-descriptor image is one block
    bn = min(vc.rows(jnp.float32) * 4, ((N + 31) // 32) * 32)
    bk = 128
    n_pad = (-N) % bn
    d = jnp.pad(descs.astype(jnp.float32), ((0, 0), (0, n_pad), (0, 0)))
    w = jnp.pad(w, ((0, 0), (0, n_pad)))
    c, c2 = _pad_codebook(centroids, bk)
    kp = c.shape[0]

    # weights ride as (bn, 1) columns and each image's histogram as a
    # (1, kp) row of a (B, 1, kp) array: both minor dims stay whole or
    # (8, 128)-aligned for any B and bn
    h = pl.pallas_call(
        functools.partial(_hist_kernel, bk=bk, kp=kp),
        grid=(B, d.shape[1] // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((1, bn, D), lambda b, n, k: (b, n, 0)),
            pl.BlockSpec((1, bn, 1), lambda b, n, k: (b, n, 0)),
            pl.BlockSpec((bk, D), lambda b, n, k: (k, 0)),
            pl.BlockSpec((1, bk), lambda b, n, k: (0, k)),
        ],
        out_specs=pl.BlockSpec((1, 1, kp), lambda b, n, k: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),
            pltpu.VMEM((bn, 1), jnp.int32),
        ],
        interpret=vc.run_interpret,
    )(d, w[..., None], c, c2)
    h = h[:, 0, :K]
    if normalize:
        h = h / jnp.maximum(jnp.sum(h, axis=1, keepdims=True), 1e-6)
    return h


def _score_kernel(h_ref, w_ref, b_ref, s_ref):
    s = jax.lax.dot_general(h_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
    s_ref[...] = s + b_ref[...]                        # (1, C) row


@functools.partial(jax.jit, static_argnames=("vc",))
def linear_score(hists: Array, w: Array, b: Array, *,
                 vc: VectorConfig = VectorConfig()) -> Array:
    """Fused one-vs-rest linear scoring: hists (B, K), w (C, K), b (C,)
    -> decision scores (B, C) f32 in one launch, weights VMEM-resident.

    Every operand is one whole-array block (no padding): the contraction
    then has exactly the staged oracle's shapes, which is what keeps the
    scores bit-identical to `ref.svm_decision_ref`.  The batch of
    histograms is VMEM-resident too (B * K * 4 bytes; 64 KiB per 64
    images at k=250).
    """
    B, K = hists.shape
    C = w.shape[0]
    return pl.pallas_call(
        _score_kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((B, K), lambda i: (0, 0)),
            pl.BlockSpec((C, K), lambda i: (0, 0)),
            pl.BlockSpec((1, C), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((B, C), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, C), jnp.float32),
        interpret=vc.run_interpret,
    )(hists.astype(jnp.float32), w.astype(jnp.float32),
      b.astype(jnp.float32)[None, :])
