"""Package-split guarantees for `repro.kernels.stencil`.

Two freezes the layered refactor must never silently break:

  1. **API freeze** — the monolith's public surface survives the package
     split exactly (cv/, serve/, benchmarks and the tests import these
     names; a missing or renamed symbol is an API break, a NEW public
     name is a deliberate surface change that must be added here).
  2. **Tile-width sweep** — the tiled2d plan is bit-identical to the
     `ref.chain_ref` oracle for every Stage kind (including the gather
     stages, whose per-tile column origins `co_t = co0 + t*cstep` are the
     tiled planner's one genuinely new coordinate rule) across tile
     widths that do and do not divide W, plus the degenerate full-width
     tile (which must reproduce the untiled streaming geometry exactly).

The sweep pins the integer (u8) carrier bit-exactly for every
non-accumulating stage; float-ACCUMULATING stages carry the repo's
documented oracle tolerance (u8: a .5 rounding tie may land 1 apart;
f32 under a multi-tile grid: 1 ulp of XLA-CPU FMA-contraction drift —
the same class of drift the streaming and window plans already show
against `chain_ref` at some widths).  Every plan-to-plan claim stays
hard: the full-width tile must BE the untiled streaming program, and
tiled2d must match streaming bit-for-bit on integer carriers.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, stencil

# ---------------------------------------------------------------------------
# 1. API freeze
# ---------------------------------------------------------------------------

# the frozen public surface (sorted): update ONLY on a deliberate API change
STENCIL_PUBLIC_API = (
    "DEGRADATION_LADDER", "MODES", "Stage", "WIDENING_OPS",
    "affine_disp_bound", "affine_stage", "box_stage",
    "chain_accumulated_halo", "chain_halo", "chain_iface",
    "chain_stream_plan", "chained_launches", "count_pallas_calls",
    "default_chain_mode", "default_ladder", "dilate_stage", "driver",
    "erode_stage", "exec_ref", "exec_streaming", "exec_window",
    "filter_stage", "fused_chain", "gaussian_stage", "grad_stage", "ir",
    "ladder", "plan", "pyr_down_stage", "pyr_up_stage",
    "remap_stage", "resize2_stage",
    "resolve_chain", "resolve_rungs", "sep_filter_stage",
    "set_default_chain_mode",
    "set_default_ladder", "sobel_stage", "stage_out_hw",
    "threshold_stage", "validate_next_base", "warp_affine_stage",
)


def test_api_freeze():
    public = tuple(sorted(n for n in dir(stencil) if not n.startswith("_")))
    missing = set(STENCIL_PUBLIC_API) - set(public)
    added = set(public) - set(STENCIL_PUBLIC_API)
    assert not missing, f"package split dropped public names: {sorted(missing)}"
    assert not added, (f"new public names {sorted(added)} — if deliberate, "
                       "freeze them in STENCIL_PUBLIC_API")


def test_api_modes_and_ladder_pinned():
    assert stencil.MODES == ("streaming", "tiled2d", "window", "ref")
    assert stencil.DEGRADATION_LADDER == ("streaming", "tiled2d", "window",
                                          "ref")


def test_api_private_compat_names():
    # non-public names with cross-module consumers (erode.py, tests):
    # keep importable from the package root
    for name in ("_apply_morph", "_GATHER_OPS", "_N_WEIGHTS", "_STRIDES",
                 "_UPSAMPLES"):
        assert hasattr(stencil, name), name


# ---------------------------------------------------------------------------
# 2. tiled2d tile-width sweep vs the chain_ref oracle
# ---------------------------------------------------------------------------

H, W = 80, 320           # W = 320: 128 does NOT divide it, 160/80 do
# (label, chain builder, float-accumulating?) — one case per Stage kind
CASES = [
    ("filter2d", lambda: (stencil.filter_stage(
        jnp.asarray(np.outer([1, 2, 1], [1, 2, 1]) / 16.0, jnp.float32)),),
     False),
    ("sep_filter", lambda: (stencil.gaussian_stage(7, 1.4),), True),
    ("erode", lambda: (stencil.erode_stage(2),), False),
    ("dilate", lambda: (stencil.dilate_stage(1),), False),
    ("box", lambda: (stencil.box_stage(3),), False),
    ("threshold", lambda: (stencil.threshold_stage(90.0),), False),
    ("affine", lambda: (stencil.affine_stage(1.1, -5.0),), True),
    ("grad_mag", lambda: (stencil.grad_stage(),), True),
    ("sobel", lambda: (stencil.sobel_stage(),), True),
    ("pyr_down", lambda: (stencil.pyr_down_stage(),), True),
    ("resize2", lambda: (stencil.resize2_stage(),), False),
    ("pyr_up", lambda: (stencil.pyr_up_stage(),), True),
    ("warp_affine", lambda: (stencil.warp_affine_stage(
        (1.0, 0.01, -1.0, -0.01, 1.0, 1.0), shape=(H, W)),), True),
    ("remap", lambda: (_remap_stage(),), True),
]


def _remap_stage():
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    return stencil.remap_stage(xx + 1.2 * np.cos(yy / 5.0),
                               yy + 1.5 * np.sin(xx / 7.0))


def _ulp_leq_1(got, want) -> bool:
    g, w = np.asarray(got), np.asarray(want)
    if g.dtype == np.uint8:
        # u8 oracle tolerance: a .5 rounding tie may land 1 apart
        return bool((np.abs(g.astype(np.int32) - w.astype(np.int32)) <= 1).all())
    # "within 1 ulp": stepping each float one representable value toward
    # the other must cross it
    return bool(((g == w) | (np.nextafter(g, w) == w)).all())


def _run_case(chain, img, tile_w, exact):
    want = ref.chain_ref(img, chain)
    got = stencil.fused_chain(img, chain, mode="tiled2d", tile_w=tile_w)
    wants = want if isinstance(want, tuple) else (want,)
    gots = got if isinstance(got, tuple) else (got,)
    assert len(gots) == len(wants)
    for g, w in zip(gots, wants):
        assert g.shape == w.shape and g.dtype == w.dtype
        if exact:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            assert _ulp_leq_1(g, w), \
                f"tiled2d (tile_w={tile_w}) drifted past the oracle tolerance"


@pytest.fixture(scope="module")
def u8_img():
    return jnp.asarray(np.random.default_rng(11).integers(
        0, 255, (H, W), dtype=np.uint8))


@pytest.fixture(scope="module")
def f32_img():
    return jnp.asarray(np.random.default_rng(12).random((H, W), np.float32))


@pytest.mark.parametrize("tile_w", [128, 160, W, None],
                         ids=["nondiv128", "div160", "fullW", "autotuned"])
@pytest.mark.parametrize("name,make,accum", CASES,
                         ids=[c[0] for c in CASES])
def test_tile_sweep_u8(u8_img, name, make, accum, tile_w):
    """Integer carrier: every Stage kind matches chain_ref at every tile
    width — dividing, non-dividing, full-width, autotuned.  Bit-identical
    except the float-accumulating stages' documented .5-tie tolerance;
    plan-to-plan (vs streaming) is bit-identical unconditionally."""
    chain = make()
    _run_case(chain, u8_img, tile_w, exact=not accum)
    got = stencil.fused_chain(u8_img, chain, mode="tiled2d", tile_w=tile_w)
    want = stencil.fused_chain(u8_img, chain, mode="streaming")
    for g, w in zip(got if isinstance(got, tuple) else (got,),
                    want if isinstance(want, tuple) else (want,)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("tile_w", [128, W], ids=["nondiv128", "fullW"])
@pytest.mark.parametrize("name,make,accum", CASES,
                         ids=[c[0] for c in CASES])
def test_tile_sweep_f32(f32_img, name, make, accum, tile_w):
    """Float carrier: non-accumulating stages stay bit-identical to
    chain_ref.  The accumulating stages pin plan-to-plan instead — vs
    the streaming plan, full-width tiles are bit-identical (the untiled
    program) and multi-tile allows 1 ulp of FMA-contraction drift —
    because their distance to the oracle is owned by the streaming plan
    (warp's fractional-coordinate caveat etc.), not by tiling, and this
    test must fail if tiling ever ADDS drift."""
    chain = make()
    if not accum:
        _run_case(chain, f32_img, tile_w, exact=True)
        return
    got = stencil.fused_chain(f32_img, chain, mode="tiled2d", tile_w=tile_w)
    want = stencil.fused_chain(f32_img, chain, mode="streaming")
    for g, w in zip(got if isinstance(got, tuple) else (got,),
                    want if isinstance(want, tuple) else (want,)):
        assert g.shape == w.shape and g.dtype == w.dtype
        if tile_w >= W:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            assert _ulp_leq_1(g, w), \
                f"tiling added drift vs streaming (tile_w={tile_w})"


def test_tile_full_width_is_untiled_program(f32_img):
    """tile_w >= W (and tile_w=None resolving to full width on narrow
    images) must reproduce the streaming plan bit-for-bit — the tiled
    planner's degenerate single-tile geometry IS the untiled geometry."""
    chain = (stencil.gaussian_stage(7, 1.4), stencil.grad_stage())
    a = stencil.fused_chain(f32_img, chain, mode="tiled2d", tile_w=W)
    b = stencil.fused_chain(f32_img, chain, mode="streaming")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tile_stride_divisibility_raises(u8_img):
    with pytest.raises(ValueError, match="divisible"):
        stencil.fused_chain(u8_img, (stencil.pyr_down_stage(),),
                            mode="tiled2d", tile_w=65)


def test_tile_w_rejected_outside_tiled2d(u8_img):
    with pytest.raises(ValueError, match="tile_w"):
        stencil.fused_chain(u8_img, (stencil.box_stage(3),),
                            mode="streaming", tile_w=64)
