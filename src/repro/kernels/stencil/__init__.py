"""Fused multi-stage stencil-pipeline engine — one Pallas launch, one VMEM
residency per image pipeline.

The paper's lever is widening the register block (LMUL m1 -> m4) so
per-instruction overhead amortizes against the register budget. These
stencils are memory-bound (arXiv 2305.09266), so the next levers on TPU
are eliminating redundant HBM traffic and giving the grid parallel width:
a chain of image ops (blur -> erode -> threshold) classically costs one
kernel launch *per op, per channel, per image*, with every intermediate
doing a full HBM round trip.  This package compiles a *chain* of stages
over a batched, multi-channel image into a **single `pallas_call`**:

  * the input is normalized to planes `(N, H, W)` (N = batch x channels)
    and the grid is `(N, n_tiles, n_bands)` — the per-channel / per-image
    Python loops of the old wrappers become grid dimensions;
  * each grid step DMAs **one** overlapping window of input rows
    (`pl.Element` offsets, rounded up to the sublane tile) sized by the
    backward recurrence
    `R_in = R_out * stride + 2*halo` over the whole chain, so a band's
    bytes cross HBM->VMEM once;
  * every stage runs in-register/in-VMEM on the band, consuming its own
    halo, and only the final output rows are written back to HBM.

Layered layout (the module map):

  * `ir`             — Stage kinds, builders, `resolve_chain` (the band
                       arity walk), `validate_next_base`, displacement
                       bounds.  Importable without Pallas or `repro.core`.
  * `plan`           — ALL row/column geometry: `chain_iface`,
                       `chain_stream_plan`, `stage_out_hw`, halo and
                       working-set accounting, lmul/plane-block/tile-width
                       selection, and `build_chain_geom` -> `ChainGeom`
                       (the full launch plan, column-tile parameterized).
  * `exec_window`    — stage bodies + the overlapping-window executor +
                       the shared pallas_call launcher.
  * `exec_streaming` — the row-carry executor (streaming & tiled2d).
  * `exec_ref`       — the staged `ref.chain_ref` floor (no launch).
  * `ladder`         — plan registry, process defaults, the degradation
                       ladder (`streaming -> tiled2d -> window -> ref`).
  * `driver`         — `fused_chain` / `chained_launches`: plan
                       resolution, plane normalization, the rung loop.

Border semantics: the chain is computed on the edge-replicated *extended
domain* — stage s sees stage s-1's values computed at out-of-image
coordinates from the edge-padded input, not an edge-replication of stage
s-1's output. For a single stage this is exactly OpenCV BORDER_REPLICATE
(matches `kernels/ref.py`); for multi-stage chains it matches
`ref.chain_ref`, and differs from the staged baseline only inside the
accumulated-halo border ring.  (On u8 carriers, float-accumulating stages
may differ from the oracle by 1 where the kernel's FMA ordering lands a
1-ulp different value on a .5 rounding tie — morphology/threshold-only
chains are bit-exact.)  Strided stages decimate on image-aligned
coordinates (even rows/cols of the *image*, as OpenCV pyrDown does),
which the geometry planning guarantees by making the pad offsets
divisible by the total stride product — per tile, under tiled2d.  See
EXPERIMENTS.md §Perf for the band/halo diagram and the stage table.

Execution modes (`fused_chain(..., mode=)`):

  * **streaming** (default when the chain has row halo) — the sequential
    row-axis grid carries each live band's already-computed rows across
    grid steps in persistent VMEM scratch rings, so each step computes
    only the *new* `rows` output rows per stage and reads the halo
    overlap from the ring instead of recomputing it from the enlarged
    window.  Step 0 runs the window path and primes the rings.
  * **tiled2d** — streaming plus a column-tile grid axis: the width
    splits into autotuned tiles, each with its own padded window, ring
    state and column origins (gathers receive per-tile origins from the
    plan).  Shrinking the per-step width buys working-set headroom, so
    deep chains reach larger lmul — residency *and* parallel width.
  * **window** — the overlapping-window model: every grid step DMAs the
    full accumulated-halo window and recomputes each stage's halo rows.
    Identical results, no carried state.
  * **ref** — the staged `ref.chain_ref` jnp path (no Pallas launch).
  * `mode=None` consults `autotune.measure_chain`'s cached winner for
    this (chain, shape, dtype, backend), else picks streaming/window by
    the halo heuristic.

Block-width selection: `vc=None` autotunes via `plan.chain_working_set` —
the largest lmul whose accumulated-halo, widened, band-count-aware
working set fits VMEM (the paper's m8 ceiling, chain-aware; the carrying
modes charge the strictly smaller ring footprint), with the tiled2d tile
width picked jointly (`plan.pick_tile_plan`)."""

from . import exec_ref, exec_streaming, exec_window, ir, ladder, plan  # noqa: F401
from .driver import (chained_launches, count_pallas_calls, fused_chain,  # noqa: F401
                     launch_count, reset_launch_counter)
from .exec_window import _apply_morph  # noqa: F401  (erode.py + tests use it)
from .ir import (Stage, _GATHER_OPS, _N_WEIGHTS, _STRIDES,  # noqa: F401
                 _UPSAMPLES, WIDENING_OPS, affine_disp_bound, affine_stage,
                 box_stage, dilate_stage, erode_stage, filter_stage,
                 gaussian_stage, grad_stage, pyr_down_stage, pyr_up_stage,
                 remap_stage, resize2_stage, resolve_chain, sep_filter_stage,
                 sobel_stage, threshold_stage, validate_next_base,
                 warp_affine_stage)
from .ladder import (DEGRADATION_LADDER, MODES, default_chain_mode,  # noqa: F401
                     default_ladder, resolve_rungs, set_default_chain_mode,
                     set_default_ladder)
from .plan import (chain_accumulated_halo, chain_halo, chain_iface,  # noqa: F401
                   chain_stream_plan, stage_out_hw)
