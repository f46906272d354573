"""Window executor — stage bodies + the overlapping-window plan + the
shared `pallas_call` launcher every Pallas plan uses.

The in-kernel stage bodies each map an (R_in, WP) band to its output-rows
band in the band's dtype; widened f32 intermediates never leave VMEM.
`window_pass` runs the whole chain over one DMA'd window (recomputing each
stage's halo rows per grid step — the PR-1..3 model) and doubles as the
streaming plan's ring-priming step 0 (`prime=True`), so the gather stages
always prime from the true input window.  `launch` owns the pallas_call
assembly (padding, specs, grid, scratch, crops) for a `plan.ChainGeom`;
`exec_streaming` reuses it with its own kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import uintr

from .ir import _N_WEIGHTS, _gather_halo
from .plan import round_sublanes

Array = jax.Array


# stage kinds whose bodies Mosaic refuses: the gathers index a flattened
# band (`jnp.take`), and pyr_up's input window does not lower either
NO_TPU_LOWERING = frozenset({"warp_affine", "remap", "pyr_up"})


def _pack(acc: Array, carrier) -> Array:
    if carrier == jnp.uint8:
        return uintr.v_pack_u8(acc)
    return acc.astype(carrier)


def _out_shape(band, out_rows):
    return band.shape[:-2] + (out_rows, band.shape[-1])


def _materialize(band: Array) -> Array:
    """Identity reduce_window: pins the band to a buffer on XLA CPU, so the
    per-step block read (a dynamic_slice) is not re-executed once per
    consuming filter tap by loop fusion (invisible in cost_analysis;
    lax.optimization_barrier gets stripped on CPU)."""
    return jax.lax.reduce_window(band, jnp.asarray(0, band.dtype), jax.lax.add,
                                 (1,) * band.ndim, (1,) * band.ndim, "VALID")


def _expand_once(band, interp: bool):
    """Widen to f32 and, on the interpret (CPU) path, pin the result to a
    buffer: the expanded band is consumed by every filter tap, and XLA-CPU
    loop fusion would otherwise re-execute the slice+convert per tap."""
    x = uintr.v_expand_f32(band)
    return _materialize(x) if interp else x


def _apply_filter2d(band, wts, static, carrier, *, interp=False):
    (kern,) = wts
    kh, kw = kern.shape
    ph, pw = kh // 2, kw // 2
    x = _expand_once(band, interp)
    out_rows = band.shape[-2] - 2 * ph
    kern = kern.astype(jnp.float32)
    acc = jnp.zeros(_out_shape(band, out_rows), jnp.float32)
    for i in range(kh):
        rows_i = x[..., i:i + out_rows, :]
        if interp:
            rows_i = _materialize(rows_i)   # kw consumers (see _expand_once)
        for j in range(kw):
            acc = uintr.v_fma(uintr.v_shift_cols(rows_i, pw - j), kern[i, j], acc)
    return _pack(acc, carrier)


def _apply_sep_filter(band, wts, static, carrier, *, interp=False):
    kx, ky = wts
    kh, kw = ky.shape[0], kx.shape[0]
    ph, pw = kh // 2, kw // 2
    x = _expand_once(band, interp)
    kx = kx.astype(jnp.float32)
    ky = ky.astype(jnp.float32)
    rowacc = jnp.zeros_like(x)
    for j in range(kw):
        rowacc = uintr.v_fma(uintr.v_shift_cols(x, pw - j), kx[j], rowacc)
    out_rows = band.shape[-2] - 2 * ph
    acc = jnp.zeros(_out_shape(band, out_rows), jnp.float32)
    for i in range(kh):
        acc = uintr.v_fma(rowacc[..., i:i + out_rows, :], ky[i], acc)
    return _pack(acc, carrier)


def _apply_box(band, wts, static, carrier, *, interp=False):
    (r,) = static
    k = 2 * r + 1
    x = _expand_once(band, interp)
    rowacc = jnp.zeros_like(x)
    for j in range(k):
        rowacc = uintr.v_add(uintr.v_shift_cols(x, r - j), rowacc)
    out_rows = band.shape[-2] - 2 * r
    acc = jnp.zeros(_out_shape(band, out_rows), jnp.float32)
    for i in range(k):
        acc = uintr.v_add(rowacc[..., i:i + out_rows, :], acc)
    return _pack(acc * jnp.float32(1.0 / (k * k)), carrier)


# Mosaic lowers neither a strided value slice (a gather) nor a reshape that
# splits a sublane or lane pair, so decimation and interleaving are 0/1
# matmuls at f32 precision: exact, since every output is one product by
# 1.0 plus zeros.
def _pairing(shape, long_axis: int, phase: int = 0) -> Array:
    """0/1 f32 matrix: one where the index along `long_axis` is twice the
    other index plus `phase` (picks, or places, every other row)."""
    long = jax.lax.broadcasted_iota(jnp.int32, shape, long_axis)
    short = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - long_axis)
    return (long == 2 * short + phase).astype(jnp.float32)


def _left(m: Array, x: Array) -> Array:
    """m @ plane for every (R, W) plane of x."""
    planes = x.reshape((-1,) + x.shape[-2:])
    out = jnp.stack([jnp.dot(m, planes[i], precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
                     for i in range(planes.shape[0])])
    return out.reshape(x.shape[:-2] + out.shape[-2:])


def _right(x: Array, m: Array) -> Array:
    """x @ m over consecutive column chunks of m's height, so each chunk
    lands as whole lanes (strided chains pad widths to lane x stride)."""
    c = m.shape[0]
    rows = x.reshape((-1, x.shape[-1]))
    out = jnp.concatenate(
        [jnp.dot(rows[:, j:j + c], m, precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
         for j in range(0, x.shape[-1], c)], axis=-1)
    return out.reshape(x.shape[:-1] + out.shape[-1:])


def _even_rows(x: Array) -> Array:
    """x[..., 0::2, :]"""
    r = x.shape[-2]
    return _left(_pairing((r // 2, r), 1), x)


def _even_cols(x: Array) -> Array:
    """x[..., 0::2]"""
    c = min(x.shape[-1], 256)
    return _right(x, _pairing((c, c // 2), 0))


def _interleave_rows(ev: Array, od: Array) -> Array:
    """Rows ev[0], od[0], ev[1], od[1], ..."""
    shape = (2 * ev.shape[-2], ev.shape[-2])
    return _left(_pairing(shape, 0), ev) + _left(_pairing(shape, 0, 1), od)


def _interleave_cols(ev: Array, od: Array) -> Array:
    """Columns ev[0], od[0], ev[1], od[1], ..."""
    c = min(ev.shape[-1], 128)
    return (_right(ev, _pairing((c, 2 * c), 1))
            + _right(od, _pairing((c, 2 * c), 1, 1)))


def _apply_pyr_down(band, wts, static, carrier, *, interp=False):
    """5-tap separable Gaussian, then decimation of even rows/cols.  The
    planner sizes the band so the valid output has exactly 2x the output
    rows, and places it so local-even rows/cols are image-even."""
    (k1,) = wts
    x = _expand_once(band, interp)
    k1 = k1.astype(jnp.float32)
    rowacc = jnp.zeros_like(x)
    for j in range(5):
        rowacc = uintr.v_fma(uintr.v_shift_cols(x, 2 - j), k1[j], rowacc)
    out_rows = band.shape[-2] - 4
    acc = jnp.zeros(_out_shape(band, out_rows), jnp.float32)
    for i in range(5):
        acc = uintr.v_fma(rowacc[..., i:i + out_rows, :], k1[i], acc)
    return _pack(_even_cols(_even_rows(acc)), carrier)


def _apply_resize2(band, wts, static, carrier, *, interp=False):
    """2x2-mean downsample: row pairs + lane-shifted column pairs, * 0.25."""
    x = _expand_once(band, interp)
    # row pairs: each row plus its successor, even rows kept
    r = _even_rows(uintr.v_add(x, uintr.v_shift_rows(x, -1)))
    c = uintr.v_add(r, uintr.v_shift_cols(r, -1))
    # scale before the (exact) selection: the selection matmul then ends
    # the stage, so no consumer can fuse the scale into its own FMAs
    return _pack(_even_cols(c * jnp.float32(0.25)), carrier)


def _apply_pyr_up(band, carrier, meta, *, interp=False):
    """2x upsample: separable even/odd phases ([1,6,1]/8 and [4,4]/8)
    interleaved in VMEM.  Row phases are sliced to the (phase, rows) window
    the planner's inverted recurrence planned; columns keep full (doubled)
    width with the wrap-contaminated edge lanes inside the column halo."""
    p2, r_out = meta
    x = _expand_once(band, interp)
    rows = band.shape[-2]
    a = x[..., 0:rows - 2, :]
    b = x[..., 1:rows - 1, :]
    c = x[..., 2:rows, :]
    ev = (a + 6.0 * b + c) * jnp.float32(0.125)
    od = (b + c) * jnp.float32(0.5)
    t = _interleave_rows(ev, od)[..., p2:p2 + r_out, :]
    if interp:
        t = _materialize(t)     # both column phases consume every row
    left, right = uintr.v_shift_cols(t, 1), uintr.v_shift_cols(t, -1)
    evc = (left + 6.0 * t + right) * jnp.float32(0.125)
    odc = (t + right) * jnp.float32(0.5)
    return _pack(_interleave_cols(evc, odc), carrier)


def _bilinear_band(x, sy, sx, oy, ox, carrier, *, interp=False):
    """Bilinear gather from an f32 band: sample the (..., R, W) band (whose
    local origin sits at *image* coordinates (oy, ox); oy and ox may be
    traced) at image coordinates (sy, sx) of shape (r_out, W).

    floor/frac are taken on the *global* coordinate (exact in f32 at image
    scales), never on the window-local one — subtracting a different
    integer origin in the kernel vs the oracle would round fy/fx apart by
    an ulp and flip u8 .5 ties.  Taps are clamped into the band; the chain
    planner's bound validation guarantees the clamp never fires for any
    output a later stage (or the final crop) consumes."""
    rows, wp = x.shape[-2], x.shape[-1]
    iy, ix = jnp.floor(sy), jnp.floor(sx)
    fy, fx = sy - iy, sx - ix
    ly = jnp.clip(iy.astype(jnp.int32) - oy, 0, rows - 2)
    lx = jnp.clip(ix.astype(jnp.int32) - ox, 0, wp - 2)
    if interp:
        x = _materialize(x)     # four gather consumers
    flat = x.reshape(x.shape[:-2] + (rows * wp,))

    def take(dy, dx):
        idx = (ly + dy) * wp + (lx + dx)
        v = jnp.take(flat, idx.reshape(-1), axis=-1, mode="clip")
        return v.reshape(x.shape[:-2] + idx.shape)

    v00, v01 = take(0, 0), take(0, 1)
    v10, v11 = take(1, 0), take(1, 1)
    top = v00 + (v01 - v00) * fx
    bot = v10 + (v11 - v10) * fx
    return _pack(top + (bot - top) * fy, carrier)


def _tile_origin(meta, tile_j):
    """Column origin of this grid step's tile: static for one tile
    (cstep == 0 keeps the historical constant-origin trace), else offset
    by the tile index at the stage's resolution."""
    mult, off, co0, cstep = meta
    co = co0 if cstep == 0 else co0 + tile_j * cstep
    return mult, off, co


def _apply_warp(band, static, carrier, meta, band_i, tile_j, *, interp=False):
    """Inverse-map affine gather: src coords are affine in the output's
    absolute image coordinates, recovered from the grid step (band_i,
    tile_j) and the planner's static (row step, row offset, col origin,
    col origin step) meta."""
    m00, m01, m02, m10, m11, m12, by, bx = static
    hy, hx = _gather_halo(by, bx)
    mult, off, co = _tile_origin(meta, tile_j)
    oy = band_i * mult + off
    out_rows = band.shape[-2] - 2 * hy
    yy = (oy + hy + jnp.arange(out_rows, dtype=jnp.int32))[:, None]
    xx = (co + jnp.arange(band.shape[-1], dtype=jnp.int32))[None, :]
    yf, xf = yy.astype(jnp.float32), xx.astype(jnp.float32)
    sx = xf * m00 + yf * m01 + m02
    sy = xf * m10 + yf * m11 + m12
    x = _expand_once(band, interp)
    return _bilinear_band(x, sy, sx, oy, co, carrier, interp=interp)


def _apply_remap(band, wts, static, carrier, meta, band_i, tile_j, *,
                 interp=False):
    """Precomputed-map gather: the (H, W) map planes ride along as per-step
    chain inputs; lookups at halo-ring (out-of-image) output coordinates
    clamp to the map edge (replicate), which the stage's extend= budget
    covers."""
    map_x, map_y = wts
    hm, wm = map_y.shape
    by, bx, ey, ex = static
    hy, hx = _gather_halo(by + ey, bx + ex)
    mult, off, co = _tile_origin(meta, tile_j)
    oy = band_i * mult + off
    out_rows = band.shape[-2] - 2 * hy
    yy = (oy + hy + jnp.arange(out_rows, dtype=jnp.int32))[:, None]
    xx = (co + jnp.arange(band.shape[-1], dtype=jnp.int32))[None, :]
    idx = (jnp.clip(yy, 0, hm - 1) * wm + jnp.clip(xx, 0, wm - 1)).reshape(-1)
    sy = jnp.take(map_y.reshape(-1), idx, mode="clip").reshape(out_rows, -1)
    sx = jnp.take(map_x.reshape(-1), idx, mode="clip").reshape(out_rows, -1)
    x = _expand_once(band, interp)
    return _bilinear_band(x, sy, sx, oy, co, carrier, interp=interp)


def _morph_identity(dtype, op):
    """Identity element of min/max for the carrier dtype."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if op == "erode" else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if op == "erode" else info.min


def _apply_morph(band, wts, static, carrier, *, op, interp=False):
    (r,) = static
    if r == 0:
        return band
    if interp:
        # Interpret (CPU emulation) lowering: one windowed reduction. Rows
        # consume the halo (valid); columns keep full width by padding with
        # the min/max identity — those edge lanes lie inside the chain's
        # accumulated column halo and never reach the crop. reduce_window
        # materializes its operand, which stops XLA-CPU loop fusion from
        # re-deriving the whole upstream stage once per window tap
        # (O(window^2) recompute); Mosaic cannot lower reduce_window, so the
        # TPU path below keeps the paper's v_min/vslide intrinsic form.
        init = jnp.asarray(_morph_identity(band.dtype, op), band.dtype)
        comp = jax.lax.min if op == "erode" else jax.lax.max
        window = (1,) * (band.ndim - 2) + (2 * r + 1, 2 * r + 1)
        pad = ((0, 0),) * (band.ndim - 1) + ((r, r),)
        return jax.lax.reduce_window(band, init, comp, window,
                                     (1,) * band.ndim, pad)
    red = uintr.v_min if op == "erode" else uintr.v_max
    narrow = band.dtype
    if jnp.issubdtype(narrow, jnp.integer) and jnp.dtype(narrow).itemsize < 4:
        band = band.astype(jnp.int32)   # no packed-int min/max on the VPU
    out_rows = band.shape[-2] - 2 * r
    # separable in-register: column min/max over 2r+1 rows, then one uniform
    # lane-shift loop over the 2r+1 column offsets (j == 0 folded in).
    acc = band[..., 0:out_rows, :]
    for i in range(1, 2 * r + 1):
        acc = red(acc, band[..., i:i + out_rows, :])
    out = None
    for j in range(2 * r + 1):
        shifted = uintr.v_shift_cols(acc, r - j)
        out = shifted if out is None else red(out, shifted)
    return out.astype(narrow)


def _apply_threshold(band, wts, static, carrier, *, interp=False):
    thresh, maxval = static
    # compare in f32: fractional thresholds must not truncate on integer
    # carriers (thresh=127.5 on u8 is x >= 128, not x > 127)
    t = jnp.float32(thresh)
    mask = uintr.v_expand_f32(band) > t
    if jnp.issubdtype(carrier, jnp.integer):
        # select on i32 and narrow: the f32 compare's mask cannot be
        # relaid out to a packed-int select on TPU
        info = jnp.iinfo(carrier)
        hi = min(max(int(maxval), info.min), info.max)
        out = uintr.v_select(mask, jnp.int32(hi), jnp.int32(0))
        return out.astype(carrier)
    hi = jnp.asarray(maxval).astype(carrier)
    lo = jnp.asarray(0).astype(carrier)
    return uintr.v_select(mask, hi, lo)


def _apply_affine(band, wts, static, carrier, *, interp=False):
    scale, offset = static
    acc = uintr.v_fma(uintr.v_expand_f32(band), jnp.float32(scale), jnp.float32(offset))
    return _pack(acc, carrier)


def _apply_grad_mag(band, wts, static, carrier, *, interp=False):
    x = _expand_once(band, interp)
    out_rows = band.shape[-2] - 2
    dy = (x[..., 2:2 + out_rows, :] - x[..., 0:out_rows, :]) * 0.5
    dx = (uintr.v_shift_cols(x, -1) - uintr.v_shift_cols(x, 1))[..., 1:1 + out_rows, :] * 0.5
    return _pack(jnp.sqrt(dx * dx + dy * dy), carrier)


def _apply_sobel(band, *, interp=False):
    """dx = [1,2,1]^T (x) [-1,0,1], dy = transpose — widened f32 pair (signed
    gradients cannot live on a u8 carrier)."""
    x = _expand_once(band, interp)
    out_rows = band.shape[-2] - 2
    cd = uintr.v_sub(uintr.v_shift_cols(x, -1), uintr.v_shift_cols(x, 1))
    cs = uintr.v_add(uintr.v_add(uintr.v_shift_cols(x, 1), uintr.v_shift_cols(x, -1)),
                     2.0 * x)
    if interp:
        cd = _materialize(cd)   # 3 row-tap consumers each (see _expand_once)
        cs = _materialize(cs)
    dx = (cd[..., 0:out_rows, :] + 2.0 * cd[..., 1:1 + out_rows, :]
          + cd[..., 2:2 + out_rows, :])
    dy = cs[..., 2:2 + out_rows, :] - cs[..., 0:out_rows, :]
    return dx, dy


def _apply_grad_pair(dx, dy, carrier):
    """sqrt(dx^2 + dy^2) over the last two bands (the Sobel pair), packed
    back to the carrier dtype."""
    dxf = uintr.v_expand_f32(dx)
    dyf = uintr.v_expand_f32(dy)
    return _pack(jnp.sqrt(dxf * dxf + dyf * dyf), carrier)


_APPLY = {
    "filter2d": _apply_filter2d,
    "sep_filter": _apply_sep_filter,
    "erode": functools.partial(_apply_morph, op="erode"),
    "dilate": functools.partial(_apply_morph, op="dilate"),
    "threshold": _apply_threshold,
    "affine": _apply_affine,
    "grad_mag": _apply_grad_mag,
    "box": _apply_box,
    "pyr_down": _apply_pyr_down,
    "resize2": _apply_resize2,
}


def apply_stage(op, band, wts, static, dtype, meta, band_i, tile_j, interp):
    """Dispatch one stage body; gather stages take the grid coordinates
    (band_i, tile_j) to recover the band's absolute image origin."""
    if op == "warp_affine":
        return _apply_warp(band, static, dtype, meta, band_i, tile_j,
                           interp=interp)
    if op == "remap":
        return _apply_remap(band, wts, static, dtype, meta, band_i, tile_j,
                            interp=interp)
    if op == "pyr_up":
        return _apply_pyr_up(band, dtype, meta, interp=interp)
    return _APPLY[op](band, wts, static, dtype, interp=interp)


def _crop_rows(band: Array, ph: int) -> Array:
    """Crop a pass-through band's rows by the active stage's halo so the
    whole band state stays row-aligned."""
    return band if ph == 0 else band[..., ph:band.shape[-2] - ph, :]


def split_refs(refs, plan, n_out, n_ring):
    """Split a kernel's trailing refs into per-stage weight tuples, output
    refs and scratch-ring refs (the shared pallas_call layout)."""
    n_w = len(refs) - n_out - n_ring
    w_refs = refs[:n_w]
    out_refs = refs[n_w:n_w + n_out]
    ring_refs = refs[n_w + n_out:]
    wts_k, wi = [], 0
    for op, *_ in plan:
        nw = _N_WEIGHTS[op]
        wts_k.append(tuple(w_refs[wi + t][...] for t in range(nw)))
        wi += nw
    return wts_k, out_refs, ring_refs


def store_bands(out_refs, bands, store_slices):
    """Write each band's store slice (its tile interior; the full band
    untiled) to its output ref — the only HBM writes of the launch."""
    for out_ref, b, (loc0, store_w) in zip(out_refs, bands, store_slices):
        out_ref[...] = b[..., loc0:loc0 + store_w]


def read_rows(x_ref, lo: int, hi: int) -> Array:
    """Rows [lo, hi) of the DMA'd window.  Mosaic slices VMEM only on
    sublane-tile boundaries, so load the aligned cover and slice the
    value (the block itself is tile-rounded, so the cover is in bounds)."""
    lo_a = lo - lo % round_sublanes(1, x_ref.dtype)
    hi_a = round_sublanes(hi, x_ref.dtype)
    return x_ref[:, lo_a:hi_a, :][:, lo - lo_a:hi - lo_a, :]


def window_pass(x_ref, r_window, ring_refs, wts_k, plan, carrier, interp,
                band_i, tile_j, splan=None, prime=False):
    """Run the whole chain over the DMA'd window; returns the band list.
    ``prime=True`` (streaming step 0) additionally fills every scratch ring
    with the tail rows of each band's stream — exactly what step 1 must
    read."""
    # (P, R_window, WP) carrier dtype
    bands = [read_rows(x_ref, 0, r_window)]
    for k, (op, static, mode, tap, (ph, pw), meta) in enumerate(plan):
        wts = wts_k[k]
        if prime:
            # ring contents == the tail of each band's stream before
            # this stage consumed it: exactly what step 1 must read
            _, _, ring_rows, d_rows, op_rids, d_rids, _ = splan[2][k]
            srcs = (bands if mode == "map" else
                    [bands[tap]] if mode == "tap" else
                    [bands[-1]] if mode == "emit" else [])
            for rid, src in zip(op_rids, srcs):
                ring_refs[rid][...] = src[..., src.shape[-2] - ring_rows:, :]
            dsrcs = (bands if mode == "tap" else
                     bands[:-1] if mode == "emit" else [])
            for rid, src in zip(d_rids, dsrcs):
                ring_refs[rid][...] = src[..., src.shape[-2] - d_rows:, :]
        if mode == "emit":           # sobel: last band -> f32 (dx, dy)
            dx, dy = _apply_sobel(bands[-1], interp=interp)
            bands = [_crop_rows(b, ph) for b in bands[:-1]] + [dx, dy]
        elif mode == "reduce":       # grad_mag pair: last two -> one
            out = _apply_grad_pair(bands[-2], bands[-1], carrier)
            bands = [_crop_rows(b, ph) for b in bands[:-2]] + [out]
        elif mode == "tap":          # apply to band `tap`, append result
            new = apply_stage(op, bands[tap], wts, static, bands[tap].dtype,
                              meta, band_i, tile_j, interp)
            if interp:
                # a tapped band has >1 consumer (the out store + later
                # taps + per-stage crops); pin it or XLA-CPU loop fusion
                # re-derives the whole ladder per consumer (see §Perf)
                new = _materialize(new)
            bands = [_crop_rows(b, ph) for b in bands] + [new]
        else:                        # map over every band
            bands = [apply_stage(op, b, wts, static, b.dtype, meta,
                                 band_i, tile_j, interp)
                     for b in bands]
    return bands


def window_kernel(x_ref, *refs, plan, carrier, interp, n_out, store_slices,
                  r_window):
    """The overlapping-window plan: every grid step recomputes the full
    chain over its own window (no carried state)."""
    wts_k, out_refs, _ = split_refs(refs, plan, n_out, 0)
    band_i, tile_j = pl.program_id(2), pl.program_id(1)
    bands = window_pass(x_ref, r_window, (), wts_k, plan, carrier, interp,
                        band_i, tile_j)
    store_bands(out_refs, bands, store_slices)


def launch(planes: Array, stages, geom, vc, kernel) -> tuple:
    """Assemble and run the pallas_call for a planned chain: pad the
    planes to the window geometry, wire the (plane-block, tile, band)
    grid's specs and scratch rings, and crop each output band to its
    image geometry.  `kernel` is a ready kernel callable (statics baked)."""
    N, H, W = planes.shape
    g = geom
    x = jnp.pad(planes,
                ((0, g.n_pad),
                 (g.pad_top, max(0, g.t_rows - g.pad_top - H)),
                 (g.pw_l, g.pad_w - g.pw_l - W)),
                mode="edge")[:, :g.t_rows]

    w_specs, w_args = [], []
    for s in stages:
        for w in s.weights:
            w_specs.append(pl.BlockSpec(w.shape,
                                        lambda n, t, i, nd=w.ndim: (0,) * nd))
            w_args.append(w)

    # outputs carry the band index as their own axis, so a band of fewer
    # rows than the sublane tile (a strided tap's) is still a whole-array
    # minor dim; merging the two axes afterwards is a reshape
    out_specs, out_shapes, crops = [], [], []
    for bdt, rows_k, store_w, loc0, h_k, w_k, crop_off in g.outs:
        out_specs.append(pl.BlockSpec((g.P, None, rows_k, store_w),
                                      lambda n, t, i: (n, i, 0, t)))
        out_shapes.append(jax.ShapeDtypeStruct(
            (N + g.n_pad, g.n_bands, rows_k, g.n_tiles * store_w), bdt))
        crops.append((h_k, w_k, crop_off))

    # overlapping windows: element-offset blocks (row step mult0, column
    # step tile_w; Pallas needs every dim of such a block element-indexed)
    outs = pl.pallas_call(
        kernel,
        grid=((N + g.n_pad) // g.P, g.n_tiles, g.n_bands),
        in_specs=[pl.BlockSpec((pl.Element(g.P), pl.Element(g.r_block),
                                pl.Element(g.wpt)),
                               lambda n, t, i: (n * g.P, i * g.mult0,
                                                t * g.tile_w))] + w_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in g.ring_shapes],
        interpret=vc.run_interpret,
    )(x, *w_args)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    return tuple(o.reshape(o.shape[0], -1, o.shape[-1])[:N, :h_k, c0:c0 + w_k]
                 for o, (h_k, w_k, c0) in zip(outs, crops))


def execute(planes: Array, stages, geom, vc) -> tuple:
    """`ChainGeom -> callable` for the window plan."""
    store_slices = tuple((loc0, store_w)
                         for _, _, store_w, loc0, _, _, _ in geom.outs)
    kernel = functools.partial(window_kernel, plan=geom.plan,
                               carrier=planes.dtype, interp=vc.run_interpret,
                               n_out=len(geom.outs),
                               store_slices=store_slices,
                               r_window=geom.r_window)
    return launch(planes, stages, geom, vc, kernel)
