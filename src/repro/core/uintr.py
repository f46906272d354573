"""Universal intrinsics — the portable vector-op layer (OpenCV analogue).

OpenCV's hal::intrin provides v_load / v_fma / v_min / v_expand /
v_pack_u... which each backend lowers to native SIMD. The paper re-lowers
them to *register-block* (m4) RVV ops. Here the same contract: kernel
bodies in repro.kernels are written against these ops on whole VMEM tiles;
VectorConfig decides the tile granularity they lower at.

Each op documents its RVV 0.7.1 counterpart (m1 vs m4 form differs only in
the register-block suffix — exactly the paper's change).

The widening ops mirror OpenCV's extended-precision pattern (u8 source,
u16/u32/f32 accumulation) that motivated the paper's m4-not-m8 choice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


# --- loads/stores are Pallas Ref reads/writes; these are the ALU ops -------

def v_fma(a: Array, b, c: Array) -> Array:
    """d = a*b + c   (RVV: vfmadd_vv_f32m<L>/vfmacc)."""
    return a * b + c


def v_add(a, b):
    """RVV: vadd_vv_<t>m<L>."""
    return a + b


def v_sub(a, b):
    """RVV: vsub_vv_<t>m<L>."""
    return a - b


def v_mul(a, b):
    """RVV: vmul/vfmul_vv_<t>m<L>."""
    return a * b


def v_min(a, b):
    """RVV: vmin(u)_vv/vfmin_vv_<t>m<L> — the erosion primitive."""
    return jnp.minimum(a, b)


def v_max(a, b):
    """RVV: vmax(u)_vv/vfmax_vv_<t>m<L> — dilation."""
    return jnp.maximum(a, b)


def v_expand_f32(a: Array) -> Array:
    """u8 -> f32 widening (OpenCV v_expand + v_cvt chains; RVV vwadd/vfcvt).

    On RVV this is where an m4 block becomes m8 (the paper's ceiling); on
    TPU it is a 4x VMEM-footprint change of the tile (int8 packs 32
    sublanes/VREG, f32 packs 8).  Integer carriers widen through i32:
    Mosaic has no direct u8 -> f32 cast."""
    if jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(jnp.int32)
    return a.astype(jnp.float32)


def v_expand_i32(a: Array) -> Array:
    """u8 -> i32 widening (RVV vwadd.vx chains)."""
    return a.astype(jnp.int32)


def v_pack_u8(a: Array) -> Array:
    """Saturating narrow to u8 with round-to-nearest (OpenCV v_pack_u /
    vnclipu on RVV): f32/i32 -> u8."""
    if jnp.issubdtype(a.dtype, jnp.floating):
        a = jnp.round(a)
    # through i32: Mosaic has no direct f32 -> u8 cast
    return jnp.clip(a, 0, 255).astype(jnp.int32).astype(jnp.uint8)


def v_select(mask: Array, a: Array, b: Array) -> Array:
    """RVV: vmerge_vvm."""
    return jnp.where(mask, a, b)


def v_shift_rows(a: Array, n: int, fill=None) -> Array:
    """Shift tile rows (axis -2) by n (positive = toward higher index),
    replicating the edge — the tile-level analogue of OpenCV's v_extract used
    to slide a filter window (RVV: vslideup/vslidedown_vx_<t>m<L>). Leading
    axes (plane blocks) pass through untouched."""
    if n == 0:
        return a
    return jnp.roll(a, n, axis=-2) if fill is None else _shift_fill(a, n, -2, fill)


def v_shift_cols(a: Array, n: int, fill=None) -> Array:
    if n == 0:
        return a
    return jnp.roll(a, n, axis=-1) if fill is None else _shift_fill(a, n, -1, fill)


def _shift_fill(a, n, axis, fill):
    axis = axis % a.ndim
    rolled = jnp.roll(a, n, axis=axis)
    idx = jnp.arange(a.shape[axis])
    mask = (idx < n) if n > 0 else (idx >= a.shape[axis] + n)
    mask = mask.reshape([-1 if i == axis else 1 for i in range(a.ndim)])
    return jnp.where(mask, fill, rolled)


def v_reduce_min(a: Array, axis=None):
    """RVV: vredmin_vs."""
    return jnp.min(a, axis=axis)


def v_reduce_sum(a: Array, axis=None):
    """RVV: vredsum_vs (the loop the 2000s-era compilers needed unrolled!)."""
    return jnp.sum(a, axis=axis)
