"""Pallas TPU kernel: oblivious-tree GBDT ensemble prediction.

"Vectorization of Gradient Boosting of Decision Trees Prediction in the
CatBoost Library for RISC-V Processors" (arXiv:2405.11062) shows GBDT
inference on *oblivious* trees — every node at depth l of tree t shares
one (feature, threshold) split — vectorizes as bitmask leaf-index
lookups: the depth-d comparison vector IS the binary leaf index.  This
kernel evaluates a whole ensemble per batch block with every model
tensor VMEM-resident, as four MXU matmuls + two vector compares:

  1. feature gather    xs   = x @ S^T          (S one-hot per (tree, level))
  2. bitmask           bits = (xs > thr)        per-level comparisons
  3. leaf index        lidx = bits @ P          (P packs level l as 2^l)
  4. leaf expand       oh   = (g @ E == iota)   one-hot over (tree, leaf)
  5. leaf sum          s    = oh @ LV           gather-free value lookup

Every step is order-exact: xs picks single elements through {0,1}
weights, the compares are bitwise, and lidx/oh hold small integers f32
represents exactly — so fused leaf indices match `ref.gbdt_leaf_ref`
bit-for-bit (the ClassifyPlan GBDT oracle contract).  Scores sum T leaf
values per class; the summation order inside one dot may differ from the
staged `ref.gbdt_scores_ref` by float association (ulp-level), which is
why the plan's acceptance pins *leaf* identity, not score bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vector import VectorConfig

Array = jax.Array

# the 0/1 selection and packing matmuls are exact only at f32 precision
# (the TPU default for f32 is one bf16 pass, which rounds x)
_HIGHEST = jax.lax.Precision.HIGHEST


def _pad128(n: int) -> int:
    return n + (-n) % 128


def _gbdt_kernel(x_ref, s_ref, thr_ref, p_ref, off_ref, e_ref, lv_ref,
                 sc_ref, li_ref):
    x = x_ref[...]                                     # (bb, Fp) f32
    xs = jax.lax.dot_general(x, s_ref[...], (((1,), (1,)), ((), ())),
                             precision=_HIGHEST,
                             preferred_element_type=jnp.float32)
    bits = (xs > thr_ref[...][None, :]).astype(jnp.float32)
    lidx = jax.lax.dot_general(bits, p_ref[...], (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
    li_ref[...] = lidx.astype(jnp.int32)               # (bb, Tp)
    g = lidx + off_ref[...][None, :]                   # global (tree, leaf)
    gexp = jax.lax.dot_general(g, e_ref[...], (((1,), (0,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
    j = jax.lax.broadcasted_iota(jnp.int32, gexp.shape, 1)
    oh = (gexp == j.astype(jnp.float32)).astype(jnp.float32)
    sc_ref[...] = jax.lax.dot_general(oh, lv_ref[...],
                                      (((1,), (0,)), ((), ())),
                                      precision=_HIGHEST,
                                      preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("vc",))
def gbdt_score(x: Array, feat: Array, thr: Array, leaf: Array,
               base: Array, *, vc: VectorConfig = VectorConfig()):
    """x (B, F) f32, feat/thr (T, depth), leaf (T, 2^depth, C), base (C,)
    -> (scores (B, C) f32, leaf indices (B, T) i32) in one launch.

    The model tensors are packed host-side into the matmul operands the
    kernel keeps VMEM-resident; padding margins are inert by
    construction (zero selection rows, +inf pad thresholds so pad bits
    never fire, zero expansion columns)."""
    B, F = x.shape
    T, depth = feat.shape
    L = leaf.shape[1]
    C = leaf.shape[2]
    if L != 2 ** depth:
        raise ValueError(f"gbdt_score: leaf table has {L} leaves for "
                         f"depth {depth} (expected {2 ** depth})")
    TD, TL = T * depth, T * L
    fp, tdp = _pad128(F), _pad128(TD)
    tp, tlp, cp = _pad128(T), _pad128(TL), _pad128(C)

    # one-hot feature selection (TDp, Fp); pad rows select nothing
    flat_feat = feat.reshape(TD).astype(jnp.int32)
    sel = (flat_feat[:, None]
           == jnp.arange(F)[None, :]).astype(jnp.float32)
    sel = jnp.pad(sel, ((0, tdp - TD), (0, fp - F)))
    # flat thresholds; +inf pads keep pad bits at 0
    thr_f = jnp.pad(thr.reshape(TD).astype(jnp.float32), (0, tdp - TD),
                    constant_values=jnp.inf)
    # bit packer (TDp, Tp): level l of tree t contributes 2^l
    lvl = jnp.arange(TD) % depth
    tree = jnp.arange(TD) // depth
    pack = ((tree[:, None] == jnp.arange(T)[None, :])
            * (2.0 ** lvl)[:, None]).astype(jnp.float32)
    pack = jnp.pad(pack, ((0, tdp - TD), (0, tp - T)))
    # global leaf offsets t*L (pad trees offset 0 — masked by E below)
    offs = jnp.pad((jnp.arange(T) * L).astype(jnp.float32), (0, tp - T))
    # expansion (Tp, TLp): column j broadcasts tree j//L's global index
    e = ((jnp.arange(TL) // L)[None, :]
         == jnp.arange(T)[:, None]).astype(jnp.float32)
    e = jnp.pad(e, ((0, tp - T), (0, tlp - TL)))
    lv = jnp.pad(leaf.reshape(TL, C).astype(jnp.float32),
                 ((0, tlp - TL), (0, cp - C)))

    bb = vc.rows(jnp.float32) * 4
    xpad = jnp.pad(x.astype(jnp.float32), ((0, (-B) % bb), (0, fp - F)))
    scores, lidx = pl.pallas_call(
        _gbdt_kernel,
        grid=(xpad.shape[0] // bb,),
        in_specs=[
            pl.BlockSpec((bb, fp), lambda i: (i, 0)),
            pl.BlockSpec(sel.shape, lambda i: (0, 0)),
            pl.BlockSpec(thr_f.shape, lambda i: (0,)),
            pl.BlockSpec(pack.shape, lambda i: (0, 0)),
            pl.BlockSpec(offs.shape, lambda i: (0,)),
            pl.BlockSpec(e.shape, lambda i: (0, 0)),
            pl.BlockSpec(lv.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, cp), lambda i: (i, 0)),
            pl.BlockSpec((bb, tp), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((xpad.shape[0], cp), jnp.float32),
            jax.ShapeDtypeStruct((xpad.shape[0], tp), jnp.int32),
        ],
        interpret=vc.run_interpret,
    )(xpad, sel, thr_f, pack, offs, e, lv)
    return (scores[:B, :C] + base[None, :].astype(jnp.float32),
            lidx[:B, :T])
