"""Chain planner — the single home for ALL row/column geometry.

The paper fixes m4 because widened (extended-precision) intermediates
occupy 2x the registers and m8 is the ISA maximum.  The TPU analogue: a
chain declares its working set as a function of the tile size (input
windows, widened accumulators, halos, streaming carry rings); we pick the
largest lmul whose total fits the VMEM budget, with double-buffering
headroom (`pick_lmul` / `pick_chain_lmul` / `plane_block`).

On top of the block-width rule this module owns the fused chain's exact
coordinate model:

  * `chain_iface` — the backward row walk in image coordinates
    (``iface[k] = (mult, off, r)``: grid step i consumes image rows
    ``[i*mult + off, i*mult + off + r)`` at stage k's input resolution);
  * `chain_stream_plan` — the streaming carry plan (how many
    already-computed rows each stage carries across grid steps in VMEM
    scratch rings);
  * `build_chain_geom` — the full launch geometry (`ChainGeom`): grid,
    window specs, per-stage gather metas, ring allocation and per-band
    store/crop rules, now parameterized by a **column-tile axis**.

The 2D tiling model: the image width splits into `n_tiles` tiles of
`tile_w` input columns; each tile gets its own padded window of
``wpt = round_lane(pw_l + tile_w + pw_in)`` columns (the 1D column model
applied per tile), its own ring state (rings re-prime when the band axis
restarts), and its own column origin ``co_t = co0 + t*cstep`` threaded to
the gather stages through the meta tuples.  ``tile_w = W`` (one tile)
reproduces the untiled geometry *exactly* — same specs, same metas, same
stores — which is what keeps streaming/window bit-identical to tiled2d's
degenerate case.  For ``n_tiles > 1`` each tile stores only its interior
columns (a static in-kernel slice at ``loc0 = -co0`` scaled to the band's
resolution), so the tiles' outputs concatenate seamlessly along the
width axis and the final crop starts at column 0.

This module (and `ir`) must stay importable without `repro.core`:
`core.autotune` re-exports the geometry from here, so a top-level core
import would be a cycle.  `VectorConfig` is imported lazily where a
default is constructed; everywhere else the config is duck-typed
(``.lane`` / ``.rows()`` / ``.vmem_budget`` / ``.with_lmul``).
"""
from __future__ import annotations

import jax.numpy as jnp
from dataclasses import dataclass
from typing import Callable

from .ir import _GATHER_OPS, _STRIDES, WIDENING_OPS, _affine_disp_over, \
    _gather_halo, resolve_chain

LMULS = (8, 4, 2, 1)

# rows of one native (sublane, 128-lane) tile by element width: a VMEM
# block's second-minor extent must be a multiple of this for Mosaic
_SUBLANE_TILE = {1: 32, 2: 16, 4: 8}


def round_sublanes(rows: int, dtype) -> int:
    """`rows` rounded up to the dtype's native sublane tile."""
    t = _SUBLANE_TILE[jnp.dtype(dtype).itemsize]
    return rows + (-rows) % t


@dataclass(frozen=True)
class WorkingSet:
    """Bytes used per grid step as a function of the config."""
    fn: Callable[["VectorConfig"], int]
    double_buffer: bool = True       # Pallas pipelines HBM->VMEM copies

    def bytes(self, vc) -> int:
        b = self.fn(vc)
        return 2 * b if self.double_buffer else b


def pick_lmul(ws: WorkingSet, *, base=None):
    """Largest lmul whose (double-buffered, widened) working set fits VMEM."""
    if base is None:
        from repro.core.vector import VectorConfig
        base = VectorConfig()
    for lm in LMULS:
        cand = base.with_lmul(lm)
        if ws.bytes(cand) <= cand.vmem_budget:
            return cand
    return base.with_lmul(1)


def _round_lane(vc, width: int, halo: int) -> int:
    wp = width + 2 * halo
    return wp + (-wp) % vc.lane


def stage_out_hw(op: str | None, h: int, w: int) -> tuple[int, int]:
    """Output (h, w) of one stage applied to an (h, w) image: replicate-border
    halo ops preserve size; pyrDown is ceil-half (OpenCV), resize2 floor,
    pyrUp doubles exactly.  Shared by the chain compiler below and the
    cross-launch pyramid accounting (`pyramid_plan`) so per-link geometry
    can never disagree."""
    if op == "pyr_down":
        return (h + 1) // 2, (w + 1) // 2
    if op == "resize2":
        return h // 2, w // 2
    if op == "pyr_up":
        return 2 * h, 2 * w
    return h, w


@dataclass(frozen=True)
class _StageShape:
    """Minimal stage view for working-set accounting: op name + halo."""
    op: str
    halo: tuple


def chain_accumulated_halo(stages) -> tuple[int, int]:
    """(row, col) halo of the whole chain in *input-resolution* units: each
    stage's halo scaled by the net resolution factor before it (map strides
    shrink downstream halos by their stride; upsamples shrink the scale, so
    each contribution is the ceil of halo * down/up — over-padding is safe,
    the replicate extension is value-identical at every coordinate)."""
    ph = pw = 0
    ny = nx = 1          # downsample product of the map stages walked so far
    dy = dx = 1          # upsample product
    for op, mode, halo, stride, up, _, _, _ in resolve_chain(stages):
        ph += -(-halo[0] * ny // dy)
        pw += -(-halo[1] * nx // dx)
        if mode == "map":
            ny *= stride[0]
            nx *= stride[1]
            dy *= up[0]
            dx *= up[1]
    return ph, pw


def chain_iface(plan, rows: int) -> list:
    """Exact backward row walk in image coordinates: ``iface[k] = (mult,
    off, r)`` means grid step i consumes image rows ``[i*mult + off,
    i*mult + off + r)`` at stage k's input resolution; ``iface[-1]`` is the
    final output band of `rows` rows.  Subsumes ``R_in = R_out*stride +
    2*halo`` and inverts it for upsamples (``R_in = ceil(R_out/up) +
    2*halo``, phase-exact).  `plan` is a `resolve_chain` record list."""
    iface = [(rows, 0, rows)]
    for op, mode, halo, stride, up, _, _, _ in reversed(plan):
        mult, off, r = iface[0]
        h = halo[0]
        if mode == "map" and up[0] > 1:
            if mult % up[0]:
                raise ValueError(
                    f"chain upsample {op!r}: band step {mult} is not "
                    f"divisible by {up[0]} (use a larger lmul or fewer "
                    "stacked upsamples)")
            off2 = off // up[0] - h
            end2 = (off + r - 1) // up[0] + h + 1
            iface.insert(0, (mult // up[0], off2, end2 - off2))
        elif mode == "map":
            s = stride[0]
            iface.insert(0, (mult * s, s * off - h, s * r + 2 * h))
        else:
            iface.insert(0, (mult, off - h, r + 2 * h))
    return iface


def chain_stream_plan(plan, iface) -> list:
    """Streaming carry plan: per stage ``(sin_off, sin_r, ring_rows,
    d_rows)``.

    In streaming mode each grid step computes only the *new* rows of every
    stage's output stream — the ``mult`` rows the step advances by — and
    carries the halo overlap in a persistent VMEM scratch ring instead of
    recomputing it from the enlarged window.  Stage k's body input per
    step is the backward rule applied to its new-output window (the top
    ``mult_out`` rows of ``iface[k+1]``): rows ``[i*mult_k + sin_off,
    ... + sin_r)``, of which the stage's ring carries the first
    ``ring_rows = sin_r - mult_k`` (= ``2*halo``; ``2*halo + 1`` for an
    odd-phase upsample) and the upstream stage's current step supplies the
    last ``mult_k``.  ``d_rows`` is the delay FIFO depth (= the stage
    halo) that pass-through bands of a tap/emit stage carry so the whole
    band state stays row-aligned."""
    out = []
    for k, (op, mode, halo, stride, up, n_in, n_out, tap) in enumerate(plan):
        mult_k, off_k, r_k = iface[k]
        mult_o, off_o, r_o = iface[k + 1]
        top_o = off_o + r_o
        h = halo[0]
        if mode == "map" and up[0] > 1:
            sin_off = (top_o - mult_o) // up[0] - h
            sin_r = (top_o - 1) // up[0] + h + 1 - sin_off
        elif mode == "map":
            s = stride[0]
            sin_off = s * (top_o - mult_o) - h
            sin_r = s * mult_o + 2 * h
        else:
            sin_off = (top_o - mult_o) - h
            sin_r = mult_o + 2 * h
        ring_rows = sin_r - mult_k
        if sin_off + sin_r != off_k + r_k or not 0 <= ring_rows <= r_k:
            raise AssertionError(
                f"chain_stream_plan: stage {k} ({op}) carry window "
                f"[{sin_off}, {sin_off + sin_r}) misaligned with window "
                f"interface [{off_k}, {off_k + r_k})")
        out.append((sin_off, sin_r, ring_rows, h if mode != "map" else 0))
    return out


def chain_working_set(stages, width: int, in_dtype=jnp.uint8, *,
                      streaming: bool = False) -> WorkingSet:
    """Working set of a fused stage chain — mirrors the executors.

    Window (default) mode: one overlapping input window whose rows follow
    the backward recurrence ``R_in = R_out * stride + 2*halo`` (so strided
    stages account for their pre-decimation geometry), then per stage its
    in-bands and out-bands (f32 for widening ops, carrier dtype otherwise)
    times the number of live bands — a tap ladder keeps every emitted band
    VMEM-resident, so working set grows with band count — plus the packed
    output bands.

    ``streaming=True`` charges the *carry-plan* footprint instead: the
    same input window DMA, but each stage's body only holds its
    ring-plus-new-rows buffer (`chain_stream_plan`) — strictly smaller for
    deep chains, so `pick_chain_lmul` / `plane_block` can choose wider
    blocks.  ``width`` is the per-grid-step *tile* width (the full image
    width untiled; `tile_w` under the tiled2d plan).  `stages` is
    duck-typed (``.op``/``.halo``; optional ``.stride``/``.tap``).
    """
    plan = resolve_chain(stages)
    ph_in, pw_in = chain_accumulated_halo(stages)
    itemsize = jnp.dtype(in_dtype).itemsize
    # constant per-step inputs (filter taps, remap's map planes) are resident
    # every grid step — a remap's two full-size f32 map bands are the
    # dominant term and must be charged, not ignored
    w_bytes = sum(int(w.size) * jnp.dtype(w.dtype).itemsize
                  for s in stages for w in getattr(s, "weights", ()))

    def fn(vc) -> int:
        rows = vc.rows(in_dtype)
        iface = chain_iface(plan, rows)
        sp = chain_stream_plan(plan, iface) if streaming else None
        wp = _round_lane(vc, width, pw_in)
        total = iface[0][2] * wp * itemsize + w_bytes    # input window DMA
        num, den = 1, 1                # net width scale so far (down / up)
        sizes = [itemsize]                 # live-band element sizes (bytes):
        for k, (op, mode, halo, stride, up, n_in, n_out, tap) in enumerate(plan):
            wp_s = max(vc.lane, wp * den // num)        # f32 downstream
            widen = op in WIDENING_OPS
            n_part = n_in if mode == "map" else 1        # participating bands
            if sp is None:
                r_in = iface[k][2]
                out_r = iface[k + 1][2]
                # in-side: every live band is resident; each participating
                # band of a widening op also holds a full f32 expansion
                total += sum(r_in * wp_s * sz for sz in sizes)
            else:
                sin_off, r_in, ring_rows, d_rows = sp[k]
                out_r = iface[k + 1][0]                  # new rows only
                # body buffer + its scratch ring per participating band;
                # pass-through bands hold their new rows + delay FIFO
                if mode == "map":
                    total += sum((r_in + ring_rows) * wp_s * sz
                                 for sz in sizes)
                else:
                    psz = sizes[tap if mode == "tap" else -1]
                    total += (r_in + ring_rows) * wp_s * psz
                    total += sum((iface[k][0] + d_rows) * wp_s * sz
                                 for sz in sizes)
            if widen:
                total += n_part * r_in * wp_s * 4
            if mode == "emit":
                sizes = sizes[:-1] + [4, 4]
            elif mode == "reduce":
                sizes = sizes[:-2] + [itemsize]
            elif mode == "tap":
                sizes = sizes + [sizes[tap]]
            # out-side: f32 accumulators of widening participants + every
            # band packed at its own dtype, resident until the store —
            # upsampled bands are charged at their post-upsample (doubled)
            # rows and width
            wp_out = max(vc.lane, wp_s * (up[1] if mode == "map" else 1))
            if widen:
                total += n_part * out_r * wp_out * 4
            total += sum(out_r * wp_out * sz for sz in sizes)
            if mode == "map":
                num *= stride[1]
                den *= up[1]
        total += rows * wp * itemsize                    # store band(s)
        return total
    return WorkingSet(fn)


def pick_chain_lmul(stages, width: int, in_dtype=jnp.uint8, *,
                    base=None, streaming: bool = False):
    """Chain-aware block-width selection: largest lmul whose accumulated-halo,
    widened working set fits VMEM (the paper's m8 ceiling, per chain)."""
    return pick_lmul(chain_working_set(stages, width, in_dtype,
                                       streaming=streaming), base=base)


def plane_block(stages, width: int, n_planes: int, vc,
                in_dtype=jnp.uint8, *, streaming: bool = False) -> int:
    """Planes per grid step: the second register-block dimension.

    Batched/multi-channel inputs give the fused kernel an extra axis to
    amortize per-grid-step overhead over; pick the largest power-of-two
    plane count whose combined working set still fits the VMEM budget
    (same ceiling rule as the lmul knob)."""
    ws = chain_working_set(stages, width, in_dtype, streaming=streaming)
    per_plane = ws.bytes(vc)
    p = 1
    while (p * 2 <= n_planes and (p * 2) * per_plane <= vc.vmem_budget):
        p *= 2
    return p


# ---------------------------------------------------------------------------
# Column-tile planning (the tiled2d knobs)
# ---------------------------------------------------------------------------

def _tile_candidates(width: int, lane: int) -> list[int]:
    """Tile-width candidates: the full width (one tile — the untiled
    geometry) plus every lane multiple below it.  Lane multiples keep the
    per-tile windows lane-aligned and automatically satisfy the chain's
    column-stride divisibility (the lane itself must divide by the stride
    product for the chain to be lowerable at all)."""
    cands = [width]
    tw = lane
    while tw < width:
        cands.append(tw)
        tw += lane
    return cands


def pick_tile_plan(stages, width: int, in_dtype=jnp.uint8, *, base=None):
    """Joint (tile width, block width) selection for the tiled2d plan.

    Wider register blocks (lmul) amortize per-grid-step overhead but the
    streaming working set scales with lmul x tile width, so at full image
    width a deep chain is often stuck at a small lmul.  Shrinking the tile
    buys the working-set headroom back: prefer the candidate reaching the
    largest lmul, tie-break on the least total padded column work
    (``n_tiles * wpt`` — each tile re-pads its halo, so more tiles means
    more overlap columns), then on the larger tile.  The full-width
    candidate is always in the pool, so when tiling buys nothing this
    degenerates to `pick_chain_lmul` and one tile.

    Returns ``(tile_w | None, vc)`` — ``None`` means one full-width tile.
    The measured autotune (`core.autotune.measure_chain`) still arbitrates
    tiled2d against the other plans on real timings; this model only picks
    tiled2d's own geometry."""
    if base is None:
        from repro.core.vector import VectorConfig
        base = VectorConfig()
    _, pw_in = chain_accumulated_halo(stages)
    best = None
    for cand in _tile_candidates(width, base.lane):
        vc_c = pick_chain_lmul(stages, cand, in_dtype, base=base,
                               streaming=True)
        n_t = -(-width // cand)
        wpt = _round_lane(vc_c, cand, pw_in)
        key = (vc_c.lmul, -(n_t * wpt), cand)
        if best is None or key > best[0]:
            best = (key, cand, vc_c)
    _, tw, vc_pick = best
    return (None if tw >= width else tw), vc_pick


def pick_tile_w(stages, width: int, in_dtype, vc):
    """Tile width for an explicitly fixed block config: the largest
    candidate whose streaming working set fits the VMEM budget at `vc`
    (one full-width tile when it fits — the untiled geometry)."""
    for cand in sorted(_tile_candidates(width, vc.lane), reverse=True):
        ws = chain_working_set(stages, cand, in_dtype, streaming=True)
        if ws.bytes(vc) <= vc.vmem_budget:
            return None if cand >= width else cand
    return min(vc.lane, width)


# ---------------------------------------------------------------------------
# Cross-launch pyramid accounting
# ---------------------------------------------------------------------------

def pyramid_plan(chains, shape, in_dtype=jnp.float32, *,
                 streaming: bool = True, base=None) -> list[dict]:
    """Static per-link accounting for a cross-launch pyramid
    (`stencil.chained_launches`): the shrinking per-octave plane geometry,
    the block width the working-set rule picks for each link, and the
    pyramid-tail `chain_ref` fallback.

    `chains` is a sequence of stage chains where every non-final chain ends
    with a strided terminal tap (the next_base contract) — link k+1's input
    is that tap's output geometry.  Per link the record holds::

        {"shape": (h, w)    — the link's input planes,
         "halo": (ph, pw)   — its chain's accumulated halo,
         "fallback": bool   — planes <= halo: fused_chain routes this link
                              to ref.chain_ref (no launch, no working set),
         "lmul": int | None — pick_chain_lmul's choice for the link's
                              width (None when the link falls back); the
                              tail links' smaller planes admit wider
                              blocks, which is why autotune keys must be
                              per-octave-shape, not per-pyramid}

    The launch count of the pyramid is ``sum(not r["fallback"])``."""
    h, w = int(shape[0]), int(shape[1])
    out = []
    for k, stages in enumerate(chains):
        stages = tuple(stages)
        ph, pw = chain_accumulated_halo(stages)
        fallback = h <= ph or w <= pw
        vc = (None if fallback else
              pick_chain_lmul(stages, w, in_dtype, base=base,
                              streaming=streaming))
        out.append({"shape": (h, w), "halo": (ph, pw), "fallback": fallback,
                    "lmul": None if fallback else vc.lmul})
        if k < len(chains) - 1:
            # the carry band is the final stage's strided terminal tap:
            # walk the map-stage geometry, then apply the tap's own rule
            hc, wc = h, w
            for op, mode, halo, stride, up, _, _, _ in resolve_chain(stages):
                if mode == "map":
                    hc, wc = stage_out_hw(op, hc, wc)
            h, w = stage_out_hw(stages[-1].op, hc, wc)
    return out


def filter2d_working_set(width: int, ksize: int, in_dtype=jnp.uint8) -> WorkingSet:
    """Single filter2d stage: widened f32 band w/ halo + f32 accumulator."""
    h = ksize // 2
    return chain_working_set((_StageShape("filter2d", (h, h)),), width, in_dtype)


def erode_working_set(width: int, ksize: int, in_dtype=jnp.uint8) -> WorkingSet:
    """No widening: min/max closed over u8."""
    return chain_working_set((_StageShape("erode", (ksize, ksize)),), width, in_dtype)


def chain_halo(stages) -> tuple[int, int]:
    """Accumulated (row, col) halo of the whole chain, in input-resolution
    units: each stage's halo scaled by the net resolution factor before it
    (ceil of halo * downsample/upsample product — map strides grow a
    downstream halo's input-resolution cost, upsamples shrink it)."""
    return chain_accumulated_halo(stages)


# ---------------------------------------------------------------------------
# Full launch geometry: the Plan the executors consume
# ---------------------------------------------------------------------------

def _band_meta(resolved, carrier):
    """Final band descriptors: per output band (dtype, source op or None).
    The source op is set for tapped bands so their output geometry rule
    (`stage_out_hw`) and stride divisor apply; map/reduce bands are
    full-res."""
    bands = [(carrier, None)]
    for op, mode, halo, stride, up, n_in, n_out, tap in resolved:
        if mode == "emit":
            bands = bands[:-1] + [(jnp.float32, None), (jnp.float32, None)]
        elif mode == "reduce":
            bands = bands[:-2] + [(carrier, None)]
        elif mode == "tap":
            bands = bands + [(bands[tap][0], op)]
    return bands


@dataclass(frozen=True)
class ChainGeom:
    """Static launch geometry of one fused chain — everything an executor
    needs to assemble the `pallas_call` (specs, grid, kernel statics, ring
    scratch, store slices and final crops).  The grid is always 3D,
    ``(n_plane_blocks, n_tiles, n_bands)`` with the band (row) axis
    innermost/sequential so streaming rings persist across a tile's bands
    and re-prime when the tile or plane-block axis advances."""
    P: int                 # plane block (planes per grid step)
    n_pad: int             # planes padded up to a multiple of P
    n_bands: int           # row-band grid extent
    n_tiles: int           # column-tile grid extent
    tile_w: int            # tile interior width, input resolution (W untiled)
    mult0: int             # input-window row step per band
    r_window: int          # input-window rows the chain consumes
    r_block: int           # r_window rounded up to the sublane tile (the
    #                        DMA'd block; the extra rows are never read)
    pad_top: int           # rows of replicate pad above the image
    t_rows: int            # padded input height
    pw_l: int              # left column pad (stride-aligned accumulated halo)
    wpt: int               # per-tile padded window width (lane-rounded)
    pad_w: int             # total padded input width
    plan: tuple            # per-stage (op, static, mode, tap, halo, meta)
    splan: tuple | None    # streaming carry plan (None: window mode)
    ring_shapes: tuple     # per-ring ((P, rows, width), dtype)
    outs: tuple            # per band (dtype, rows_k, store_w, loc0, h_k,
    #                        w_k, crop_off): the kernel stores band columns
    #                        [loc0, loc0+store_w) as the band's grid-tile
    #                        slot; the launcher crops rows to h_k and
    #                        columns [crop_off, crop_off+w_k)


def build_chain_geom(stages, shape: tuple, dtype, vc, *, stream: bool = False,
                     tile_w: int | None = None) -> ChainGeom:
    """Plan one fused-chain launch over (N, H, W) planes.

    The planning walk (backward rows via `chain_iface`, forward columns
    with per-stage origins, gather displacement-bound validation, streaming
    ring allocation) is shared by every Pallas executor; `tile_w` switches
    on the column-tile axis (None or >= W: one full-width tile, the exact
    untiled geometry).  Raises ValueError for chain misconfiguration —
    empty output, stride/lane indivisibility, gather bounds that undershoot
    the fused window's evaluation rectangle."""
    stages = tuple(stages)
    resolved = resolve_chain(stages)
    N, H, W = shape
    ph_in, pw_in = chain_accumulated_halo(stages)
    rows = vc.rows(dtype)

    # forward geometry: final full-res image size + net map scale (down/up)
    h_fin, w_fin = H, W
    ny = nx = uy = ux = 1
    for op, mode, halo, stride, up, _, _, _ in resolved:
        if mode == "map":
            h_fin, w_fin = stage_out_hw(op, h_fin, w_fin)
            ny, nx = ny * stride[0], nx * stride[1]
            uy, ux = uy * up[0], ux * up[1]
    if h_fin < 1 or w_fin < 1:
        raise ValueError("fused_chain: chain output is empty for a "
                         f"{(H, W)} input (strided stages consumed it)")
    bands = _band_meta(resolved, dtype)
    # per-band stride divisor below the final state scale (terminal taps)
    divs = [_STRIDES.get(src_op, (1, 1)) for _, src_op in bands]
    down_y = ny * max(d for d, _ in divs)
    down_x = nx * max(d for _, d in divs)
    if rows % down_y or vc.lane % down_x:
        raise ValueError(f"chain stride product ({down_y}, {down_x}) must "
                         f"divide the band rows ({rows}) and lane ({vc.lane})")

    # column-tile normalization: one tile == the untiled geometry, exactly
    if tile_w is None or tile_w >= W:
        tile_w, n_tiles = W, 1
    else:
        if tile_w < 1:
            raise ValueError(f"fused_chain: tile_w={tile_w} must be >= 1")
        n_tiles = -(-W // tile_w)
        if tile_w % down_x:
            raise ValueError(
                f"fused_chain: tile_w={tile_w} must be divisible by the "
                f"chain's column stride product {down_x} (tile seams must "
                "land on image-aligned decimation coordinates)")

    P = plane_block(stages, tile_w, N, vc, in_dtype=dtype, streaming=stream)
    n_pad = (-N) % P

    # backward row walk in image coordinates: iface[k] = (mult, off, r)
    # means band i consumes image rows [i*mult + off, i*mult + off + r) at
    # stage k's input resolution (iface[-1] is the final output band).
    iface = chain_iface(resolved, rows)
    mult0, off0, r_window = iface[0]
    pad_top = -off0
    n_bands = max(1, -(-h_fin // rows))
    r_block = round_sublanes(r_window, dtype)
    t_rows = (n_bands - 1) * mult0 + r_block

    # column geometry, per tile: left pad divisible by the total downsample
    # product so in-kernel even-index decimation lands on even *image*
    # coordinates; every tile's window is the 1D model applied at its
    # origin, so tile t's block starts at input column t*tile_w of the
    # padded array (whose column 0 is image column -pw_l)
    pw_l = pw_in + (-pw_in) % down_x
    # (a strided chain pads to lane * stride, so every decimated band
    # stays a whole number of lanes wide)
    wpt = pw_l + tile_w + pw_in
    wpt += (-wpt) % (vc.lane * down_x)
    pad_w = (n_tiles - 1) * tile_w + wpt

    # (row, col) halo still needed *after* each stage, at its output
    # resolution — the gather stages' evaluation rectangle: outputs beyond
    # image + this ring are window slack that the final crop discards, so
    # their (clamped) gathers need no displacement budget
    needr = [0] * (len(resolved) + 1)
    needc = [0] * (len(resolved) + 1)
    for k in range(len(resolved) - 1, -1, -1):
        op, mode, halo, stride, up, _, _, _ = resolved[k]
        r, c = needr[k + 1], needc[k + 1]
        if mode == "map":
            r = -(-r // up[0]) * stride[0]
            c = -(-c // up[1]) * stride[1]
        needr[k] = halo[0] + r
        needc[k] = halo[1] + c

    # forward walk: per-stage static meta (gather coordinates, pyr_up
    # phase) + displacement-bound validation against the actual fused
    # window — a declared bound that undershoots the halo ring the later
    # stages consume would silently clamp gathers, so it raises here.
    # Gather metas carry (row step, row offset, tile-0 col origin, col
    # origin step per tile): the kernel recovers tile t's origin as
    # co0 + t*cstep (cstep = 0 untiled, keeping the origin static).
    metas = []
    stage_cos, stage_csteps, stage_wps = [], [], []
    co = -pw_l                  # image col of local col 0 at current stage
    cstep = tile_w if n_tiles > 1 else 0
    wp_cur = wpt
    h_cur, w_cur = H, W
    for k, (op, mode, halo, stride, up, _, _, _) in enumerate(resolved):
        mult_k, off_k, r_k = iface[k]
        stage_cos.append(co)
        stage_csteps.append(cstep)
        stage_wps.append(wp_cur)
        if op in _GATHER_OPS:
            metas.append((mult_k, off_k, co, cstep))
            hy, hx = halo
            cya, cxa = needr[k + 1], needc[k + 1]
            min_y = max(off_k + hy, -cya)
            max_y = min((n_bands - 1) * mult_k + off_k + r_k - hy - 1,
                        h_cur - 1 + cya)
            min_x, max_x = -cxa, w_cur - 1 + cxa
            st = stages[k].static
            if op == "warp_affine":
                m = (st[0:3], st[3:6])
                req_y, req_x = _affine_disp_over(m, min_y, max_y, min_x, max_x)
            else:
                if stages[k].weights[1].shape != (h_cur, w_cur):
                    raise ValueError(
                        "remap stage: map planes are "
                        f"{stages[k].weights[1].shape}, but the image at "
                        f"this stage is {(h_cur, w_cur)}")
                req_y = st[0] + max(0, -min_y, max_y - (h_cur - 1))
                req_x = st[1] + max(0, -min_x, max_x - (w_cur - 1))
            req_hy, req_hx = _gather_halo(req_y, req_x)
            if req_hy > hy or req_hx > hx:
                raise ValueError(
                    f"{op} stage: declared displacement bound gives halo "
                    f"({hy}, {hx}) but the fused window evaluates outputs "
                    f"over rows [{min_y}, {max_y}] x cols [{min_x}, "
                    f"{max_x}], needing displacement ({req_y:.2f}, "
                    f"{req_x:.2f}) — declare it via bound=/extend= "
                    "(downstream stages consume the halo ring)")
        elif op == "pyr_up":
            _, off_o, r_o = iface[k + 1]
            metas.append((off_o - 2 * off_k - 2, r_o))
        else:
            metas.append(None)
        if mode == "map":
            h_cur, w_cur = stage_out_hw(op, h_cur, w_cur)
            if stride[1] > 1:
                co = co // stride[1]
                cstep = cstep // stride[1]
                wp_cur = wp_cur // stride[1]
            elif up[1] > 1:
                co = co * up[1]
                cstep = cstep * up[1]
                wp_cur = wp_cur * up[1]

    plan = tuple((s.op, s.static, mode, tap, halo, meta)
                 for s, (op, mode, halo, stride, up, n_in, n_out, tap), meta
                 in zip(stages, resolved, metas))

    # streaming carry plan: scratch ring wiring per stage (see the package
    # docstring and chain_stream_plan for the row math); ring widths are
    # the per-tile stage widths, and the band axis is innermost so rings
    # re-prime at band 0 of every (plane block, tile) pair
    splan, ring_shapes = None, []
    if stream:
        sp = chain_stream_plan(resolved, iface)

        def alloc(rows_a, wp_a, dt):
            ring_shapes.append(((P, rows_a, wp_a), dt))
            return len(ring_shapes) - 1

        band_dts = [dtype]
        sstages = []
        for k, (op, mode, halo, stride, up, n_in, n_out_k, tap) \
                in enumerate(resolved):
            sin_off, sin_r, ring_rows, d_rows = sp[k]
            mult_k, off_k, r_k = iface[k]
            wp_k = stage_wps[k]
            op_rids, d_rids = (), ()
            if k > 0 and ring_rows > 0:
                # stage 0's body input is a static slice of the DMA'd
                # window itself — no ring needed for its history
                if mode == "map":
                    op_rids = tuple(alloc(ring_rows, wp_k, dt)
                                    for dt in band_dts)
                elif mode == "tap":
                    op_rids = (alloc(ring_rows, wp_k, band_dts[tap]),)
                elif mode == "emit":
                    op_rids = (alloc(ring_rows, wp_k, band_dts[-1]),)
            if d_rows > 0:
                dsrc = (band_dts if mode == "tap" else
                        band_dts[:-1] if mode == "emit" else [])
                d_rids = tuple(alloc(d_rows, wp_k, dt) for dt in dsrc)
            if op in _GATHER_OPS:
                smeta = (mult_k, sin_off, stage_cos[k], stage_csteps[k])
            elif op == "pyr_up":
                mult_o, off_o, r_o = iface[k + 1]
                p2s = (off_o + r_o - mult_o) - 2 * (sin_off + 1)
                if not 0 <= p2s <= 1:       # even/odd phase of the streamed
                    raise AssertionError(   # interface; anything else would
                        f"pyr_up stream phase {p2s} out of range")  # mis-slice
                smeta = (p2s, mult_o)
            else:
                smeta = None
            sstages.append((sin_off - off0 if k == 0 else None, sin_r,
                            ring_rows, d_rows, op_rids, d_rids, smeta))
            if mode == "emit":
                band_dts = band_dts[:-1] + [jnp.float32, jnp.float32]
            elif mode == "reduce":
                band_dts = band_dts[:-2] + [dtype]
            elif mode == "tap":
                band_dts = band_dts + [band_dts[tap]]
        if ring_shapes:
            splan = (mult0, r_window, tuple(sstages))
        # a halo-free chain carries nothing: the window pass IS minimal

    # per-band store geometry.  Untiled: the kernel stores the band's full
    # padded width and the launcher crops at the (scaled) left pad — the
    # historical layout, kept bit-for-bit.  Tiled: each tile stores only
    # its interior columns (static slice at loc0 = -co0 scaled), so tile
    # slots concatenate into a seamless width axis and the crop starts at
    # column 0; the halo/lane slack columns each tile also computed are
    # discarded in-kernel.
    outs = []
    wpt_full = wpt * ux // nx
    co_fin, cstep_fin = co, cstep
    for (bdt, src_op), (dy, dx) in zip(bands, divs):
        rows_k = rows // dy
        h_k, w_k = stage_out_hw(src_op, h_fin, w_fin)
        if n_tiles == 1:
            store_w, loc0, crop_off = wpt_full // dx, 0, -co_fin // dx
        else:
            store_w, loc0, crop_off = cstep_fin // dx, -co_fin // dx, 0
        outs.append((bdt, rows_k, store_w, loc0, h_k, w_k, crop_off))

    return ChainGeom(P=P, n_pad=n_pad, n_bands=n_bands, n_tiles=n_tiles,
                     tile_w=tile_w, mult0=mult0, r_window=r_window,
                     r_block=r_block,
                     pad_top=pad_top, t_rows=t_rows, pw_l=pw_l, wpt=wpt,
                     pad_w=pad_w, plan=plan, splan=splan,
                     ring_shapes=tuple(ring_shapes), outs=tuple(outs))
