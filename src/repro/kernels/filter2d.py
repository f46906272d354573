"""2D image filtering (OpenCV filter2D / GaussianBlur) — thin wrappers over
single-stage chains of the fused stencil engine (see stencil.py).

Band decomposition: a (planes, bands) grid where `rows = vc.rows(dtype)`
(= sublane-packing x lmul — the paper's register-block knob). The row halo
arrives in the same DMA as the band via one overlapping-window BlockSpec
(`pl.Element` offsets); the column halo is handled by pre-padding the width and
rotating lanes in-register (uintr.v_shift_cols == RVV vslide). Channels and
batch images are grid dimensions, not Python loops, so a (B, H, W, C)
input is one `pallas_call`.

Widening: u8 bands expand to f32 accumulators in VMEM — the exact
extended-precision pattern (m4 -> m8) that sets the paper's block-width
ceiling; repro.core.autotune reproduces that rule against the VMEM budget.

Two variants:
  filter2d     — kh*kw FMAs per pixel (the paper's filter2D).
  sep_filter2d — fused separable row+column pass in one VMEM residency
                 (kh+kw FMAs): a beyond-paper optimization enabled by
                 TPU's large VMEM (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import jax

from repro.core.vector import VectorConfig

from . import stencil

Array = jax.Array


def filter2d(img: Array, kernel: Array, *, vc: VectorConfig = VectorConfig()) -> Array:
    """OpenCV filter2D (correlation, BORDER_REPLICATE).

    (H, W), (H, W, C) or (B, H, W, C); bit-identical to ref.filter2d_ref.
    """
    return stencil.fused_chain(img, (stencil.filter_stage(kernel),), vc=vc)


def sep_filter2d(img: Array, kx: Array, ky: Array, *,
                 vc: VectorConfig = VectorConfig()) -> Array:
    """Fused separable filter (single HBM round-trip row+col pass)."""
    return stencil.fused_chain(img, (stencil.sep_filter_stage(kx, ky),), vc=vc)
