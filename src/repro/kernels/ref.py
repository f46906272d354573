"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics the kernels are tested against
(tests/test_kernels_*.py sweep shapes/dtypes and assert_allclose), and they
are the "SeqScalar"-rung implementations in the paper-table benchmarks
(what XLA does without the hand-written kernel).

Border policy: BORDER_REPLICATE (OpenCV default for filter2D/erode).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def _pad_replicate(img: Array, ph: int, pw: int) -> Array:
    return jnp.pad(img, ((ph, ph), (pw, pw)) + ((0, 0),) * (img.ndim - 2), mode="edge")


def filter2d_ref(img: Array, kernel: Array) -> Array:
    """2D correlation (OpenCV filter2D), single channel (H, W) or (H, W, C).

    u8 input -> f32 accumulation -> round + saturate back to u8
    (OpenCV saturate_cast semantics); float input stays float.
    """
    kh, kw = kernel.shape
    ph, pw = kh // 2, kw // 2
    x = _pad_replicate(img, ph, pw).astype(jnp.float32)
    out = jnp.zeros(img.shape, jnp.float32)
    H, W = img.shape[:2]
    for i in range(kh):
        for j in range(kw):
            out = out + kernel[i, j].astype(jnp.float32) * x[i:i + H, j:j + W]
    if img.dtype == jnp.uint8:
        return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)
    return out.astype(img.dtype)


def sep_filter2d_ref(img: Array, kx: Array, ky: Array) -> Array:
    """Separable filter: row pass kx then column pass ky (float accumulate,
    single rounding at the end — matches the fused kernel)."""
    H, W = img.shape[:2]
    pw, ph = kx.shape[0] // 2, ky.shape[0] // 2
    x = _pad_replicate(img, 0, pw).astype(jnp.float32)
    row = sum(kx[j].astype(jnp.float32) * x[:, j:j + W] for j in range(kx.shape[0]))
    row = _pad_replicate(row, ph, 0)
    out = sum(ky[i].astype(jnp.float32) * row[i:i + H] for i in range(ky.shape[0]))
    if img.dtype == jnp.uint8:
        return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)
    return out.astype(img.dtype)


def gaussian_kernel1d(ksize: int, sigma: float | None = None) -> Array:
    """OpenCV getGaussianKernel: sigma default 0.3*((ksize-1)*0.5 - 1) + 0.8."""
    if sigma is None or sigma <= 0:
        sigma = 0.3 * ((ksize - 1) * 0.5 - 1) + 0.8
    x = jnp.arange(ksize, dtype=jnp.float32) - (ksize - 1) / 2
    k = jnp.exp(-(x * x) / (2 * sigma * sigma))
    return k / jnp.sum(k)


def erode_ref(img: Array, ksize: int) -> Array:
    """Morphological erosion, (2*ksize+1)^2 rectangular structuring element
    (the paper's 'filter size' parameter is the half-width)."""
    r = ksize
    x = _pad_replicate(img, r, r)
    H, W = img.shape[:2]
    out = x[0:H, 0:W]
    for i in range(2 * r + 1):
        for j in range(2 * r + 1):
            out = jnp.minimum(out, x[i:i + H, j:j + W])
    return out.astype(img.dtype)


def dilate_ref(img: Array, ksize: int) -> Array:
    r = ksize
    x = _pad_replicate(img, r, r)
    H, W = img.shape[:2]
    out = x[0:H, 0:W]
    for i in range(2 * r + 1):
        for j in range(2 * r + 1):
            out = jnp.maximum(out, x[i:i + H, j:j + W])
    return out.astype(img.dtype)


def _saturate(out: Array, dtype) -> Array:
    if dtype == jnp.uint8:
        return jnp.clip(jnp.round(out), 0, 255).astype(jnp.uint8)
    return out.astype(dtype)


def _ref_valid_op(s, x, dtype):
    """One geometry-preserving-or-shrinking stage in valid mode on a 2D
    extended-domain array, saturating to the band dtype.  Strided ops return
    their *pre-decimation* valid result (the caller decimates phase-aligned
    to image coordinates)."""
    op = s.op
    ph, pw = s.halo
    h, w = x.shape[0] - 2 * ph, x.shape[1] - 2 * pw
    if op == "filter2d":
        k = s.weights[0].astype(jnp.float32)
        kh, kw = k.shape
        xf = x.astype(jnp.float32)
        acc = sum(k[i, j] * xf[i:i + h, j:j + w]
                  for i in range(kh) for j in range(kw))
        return _saturate(acc, dtype)
    if op in ("sep_filter", "pyr_down"):
        if op == "pyr_down":
            kx = ky = jnp.asarray([1.0, 4.0, 6.0, 4.0, 1.0], jnp.float32) / 16.0
        else:
            kx = s.weights[0].astype(jnp.float32)
            ky = s.weights[1].astype(jnp.float32)
        xf = x.astype(jnp.float32)
        row = sum(kx[j] * xf[:, j:j + w] for j in range(kx.shape[0]))
        acc = sum(ky[i] * row[i:i + h] for i in range(ky.shape[0]))
        return _saturate(acc, dtype)
    if op == "box":
        (r,) = s.static
        k = 2 * r + 1
        xf = x.astype(jnp.float32)
        row = sum(xf[:, j:j + w] for j in range(k))
        acc = sum(row[i:i + h] for i in range(k))
        return _saturate(acc * jnp.float32(1.0 / (k * k)), dtype)
    if op in ("erode", "dilate"):
        red = jnp.minimum if op == "erode" else jnp.maximum
        acc = x[0:h, 0:w]
        for i in range(2 * ph + 1):
            for j in range(2 * pw + 1):
                acc = red(acc, x[i:i + h, j:j + w])
        return acc
    if op == "threshold":
        # f32 comparison: fractional thresholds must not truncate on
        # integer carriers (127.5 on u8 means x >= 128, not x > 127)
        t, maxval = s.static
        return jnp.where(x.astype(jnp.float32) > jnp.float32(t),
                         jnp.asarray(maxval).astype(dtype),
                         jnp.asarray(0).astype(dtype))
    if op == "affine":
        scale, offset = s.static
        return _saturate(x.astype(jnp.float32) * scale + offset, dtype)
    if op == "grad_mag":          # single-band central-difference form
        xf = x.astype(jnp.float32)
        dy = (xf[2:2 + h, 1:1 + w] - xf[0:h, 1:1 + w]) * 0.5
        dx = (xf[1:1 + h, 2:2 + w] - xf[1:1 + h, 0:w]) * 0.5
        return _saturate(jnp.sqrt(dx * dx + dy * dy), dtype)
    raise ValueError(f"chain_ref: unknown op {op!r}")


def _ref_sobel(x):
    """Valid-mode Sobel ksize=3 pair: dx = [1,2,1]^T (x) [-1,0,1], dy = dx^T,
    widened f32 (signed; never packed to the carrier)."""
    xf = x.astype(jnp.float32)
    h = x.shape[0] - 2
    cd = xf[:, 2:] - xf[:, :-2]
    cs = (xf[:, :-2] + xf[:, 2:]) + 2.0 * xf[:, 1:-1]
    dx = cd[0:h] + 2.0 * cd[1:1 + h] + cd[2:2 + h]
    dy = cs[2:2 + h] - cs[0:h]
    return dx, dy


def _ref_pyr_up(x):
    """Valid-mode pyrUp on an extended band: even phase [1,6,1]/8, odd phase
    [4,4]/8 per axis, interleaved — input (h, w) -> (2(h-2), 2(w-2)), the
    output origin doubling as 2*(origin+1).  Arithmetic mirrors the fused
    kernel expression-for-expression so f32 results are bit-identical."""
    xf = x.astype(jnp.float32)
    a, b, c = xf[:-2], xf[1:-1], xf[2:]
    ev = (a + 6.0 * b + c) * jnp.float32(0.125)
    od = (b + c) * jnp.float32(0.5)
    t = jnp.stack([ev, od], axis=1).reshape(2 * (x.shape[0] - 2), x.shape[1])
    left, mid, right = t[:, :-2], t[:, 1:-1], t[:, 2:]
    evc = (left + 6.0 * mid + right) * jnp.float32(0.125)
    odc = (mid + right) * jnp.float32(0.5)
    u = jnp.stack([evc, odc], axis=2)
    return u.reshape(t.shape[0], 2 * (x.shape[1] - 2))


def _ref_bilinear(x, sy, sx, oy, ox):
    """Bilinear sample of the extended band x (local origin at image (oy,
    ox)) at image coordinates (sy, sx), replicate-clamped to the band.
    floor/frac on the *global* coordinate + the lerp order mirror the fused
    kernel exactly (u8 bit-exactness on .5 rounding ties)."""
    xf = x.astype(jnp.float32)
    iy, ix = jnp.floor(sy), jnp.floor(sx)
    fy, fx = sy - iy, sx - ix
    ly = jnp.clip(iy.astype(jnp.int32) - oy, 0, x.shape[0] - 2)
    lx = jnp.clip(ix.astype(jnp.int32) - ox, 0, x.shape[1] - 2)
    v00, v01 = xf[ly, lx], xf[ly, lx + 1]
    v10, v11 = xf[ly + 1, lx], xf[ly + 1, lx + 1]
    top = v00 + (v01 - v00) * fx
    bot = v10 + (v11 - v10) * fx
    return top + (bot - top) * fy


def _ref_gather(s, b, oy, ox):
    """One gather stage (warp_affine / remap) on an extended band: evaluate
    the dst->src map at the band's absolute image coordinates and sample
    bilinearly.  Output shrinks by the stage halo per side (origin moves by
    (+hy, +hx)); remap's out-of-image lookups clamp to the map edge."""
    hy, hx = s.halo
    h = b.shape[0] - 2 * hy
    w = b.shape[1] - 2 * hx
    yy = (oy + hy + jnp.arange(h, dtype=jnp.int32))[:, None]
    xx = (ox + hx + jnp.arange(w, dtype=jnp.int32))[None, :]
    if s.op == "warp_affine":
        m00, m01, m02, m10, m11, m12 = s.static[:6]
        yf, xf = yy.astype(jnp.float32), xx.astype(jnp.float32)
        sx = xf * m00 + yf * m01 + m02
        sy = xf * m10 + yf * m11 + m12
    else:
        map_x, map_y = s.weights
        hm, wm = map_y.shape
        yc = jnp.clip(yy, 0, hm - 1)
        xc = jnp.clip(xx, 0, wm - 1)
        sy = map_y[yc, xc]
        sx = map_x[yc, xc]
    out = _ref_bilinear(b, sy, sx, oy, ox)
    return _saturate(out, b.dtype), oy + hy, ox + hx


def chain_ref(img: Array, stages):
    """Oracle for kernels.stencil.fused_chain (duck-typed Stage objects).

    Semantics: compute-on-extended-domain — the input is edge-padded once by
    the chain's accumulated (stride-scaled) halo and every stage runs
    valid-mode on the extended array, with the per-stage band-dtype
    saturation the fused kernel applies.  The value flowing between stages
    is an ordered list of bands, each tracked with the image coordinate of
    its local origin so strided stages decimate on *image-even* rows/cols
    (OpenCV pyrDown alignment) regardless of how much halo is left.  For a
    single stage this coincides with the per-op refs above; multi-stage
    chains differ from staged per-op execution only inside the
    accumulated-halo border ring (see EXPERIMENTS.md §Perf).

    Returns one array, or a tuple when the chain ends with multiple live
    bands (taps / Sobel pairs), mirroring fused_chain.
    """
    stages = tuple(stages)

    # static arity walk (mirrors the stencil IR contract, derived only from
    # duck-typed stage attributes so this stays an independent oracle)
    resolved, n = [], 1
    for s in stages:
        tap = getattr(s, "tap", None)
        stride = tuple(getattr(s, "stride", (1, 1)))
        up = tuple(getattr(s, "upsample", (1, 1)))
        if s.op == "sobel":
            resolved.append(("emit", (1, 1), stride, up, None)); n += 1
        elif s.op == "grad_mag" and n >= 2:
            resolved.append(("reduce", (0, 0), stride, up, None)); n -= 1
        elif tap is not None:
            if up != (1, 1):
                raise ValueError(f"chain_ref: upsampling stage {s.op!r} does "
                                 "not support tap=")
            if not -n <= tap < n:
                raise ValueError(f"chain_ref: stage {s.op!r} tap={tap} out of "
                                 f"range for {n} live band(s)")
            resolved.append(("tap", tuple(s.halo), stride, up, tap % n)); n += 1
        else:
            resolved.append(("map", tuple(s.halo), stride, up, None))

    # accumulated halo: per-stage ceil of halo * net-downsample/net-upsample
    # (over-padding is safe: the replicate extension is value-identical at
    # every coordinate, and the final crop is origin-tracked)
    PH = PW = 0
    ny = nx = uy = ux = 1
    for mode, (ph, pw), stride, up, _ in resolved:
        PH += -(-ph * ny // uy)
        PW += -(-pw * nx // ux)
        if mode == "map":
            ny, nx = ny * stride[0], nx * stride[1]
            uy, ux = uy * up[0], ux * up[1]

    # final image geometry per band: full-res state size + strided-tap rule
    def rule(op, h, w):
        if op == "pyr_down":
            return (h + 1) // 2, (w + 1) // 2
        if op == "resize2":
            return h // 2, w // 2
        if op == "pyr_up":
            return 2 * h, 2 * w
        return h, w

    if img.ndim == 2:
        h_fin, w_fin = img.shape
    elif img.ndim == 3:
        h_fin, w_fin = img.shape[0], img.shape[1]
    else:
        h_fin, w_fin = img.shape[1], img.shape[2]
    for s, (mode, halo, stride, up, tap) in zip(stages, resolved):
        if mode == "map":
            h_fin, w_fin = rule(s.op, h_fin, w_fin)
    sizes = [(h_fin, w_fin)]
    for s, (mode, halo, stride, up, tap) in zip(stages, resolved):
        if mode == "emit":
            sizes = sizes[:-1] + [(h_fin, w_fin)] * 2
        elif mode == "reduce":
            sizes = sizes[:-2] + [(h_fin, w_fin)]
        elif mode == "tap":
            sizes = sizes + [rule(s.op, h_fin, w_fin)]

    def apply_one(s, ph, pw, stride, b, oy, ox):
        """Stage s on one band: valid op + image-phase-aligned decimation.
        Returns (array, new origin)."""
        if s.op == "resize2":
            # 2x2-mean: pairs start on even image coordinates
            xf = b.astype(jnp.float32)
            s0, s1 = (-oy) % 2, (-ox) % 2
            m = (xf.shape[0] - s0) // 2
            mw = (xf.shape[1] - s1) // 2
            rs = xf[s0:s0 + 2 * m:2] + xf[s0 + 1:s0 + 1 + 2 * m:2]
            cs = rs[:, s1:s1 + 2 * mw:2] + rs[:, s1 + 1:s1 + 1 + 2 * mw:2]
            return (_saturate(cs * jnp.float32(0.25), b.dtype),
                    (oy + s0) // 2, (ox + s1) // 2)
        if s.op == "pyr_up":
            return (_saturate(_ref_pyr_up(b), b.dtype),
                    2 * (oy + 1), 2 * (ox + 1))
        if s.op in ("warp_affine", "remap"):
            return _ref_gather(s, b, oy, ox)
        new = _ref_valid_op(s, b, b.dtype)
        noy, nox = oy + ph, ox + pw
        if stride != (1, 1):
            s0, s1 = (-noy) % stride[0], (-nox) % stride[1]
            new = new[s0::stride[0], s1::stride[1]]
            noy, nox = (noy + s0) // stride[0], (nox + s1) // stride[1]
        return new, noy, nox

    def crop(b, oy, ox, ph, pw):
        """Pass-through band: crop by the active stage's halo to stay aligned."""
        return (b[ph:b.shape[0] - ph or None, pw:b.shape[1] - pw or None],
                oy + ph, ox + pw)

    def plane_chain(x):                 # x: extended (H+2PH, W+2PW) plane
        bands = [(x, -PH, -PW)]
        for s, (mode, (ph, pw), stride, up, tap) in zip(stages, resolved):
            if mode == "emit":
                dx, dy = _ref_sobel(bands[-1][0])
                oy, ox = bands[-1][1] + 1, bands[-1][2] + 1
                bands = [crop(*b, ph, pw) for b in bands[:-1]]
                bands += [(dx, oy, ox), (dy, oy, ox)]
            elif mode == "reduce":
                (a, oy, ox), (b, _, _) = bands[-2], bands[-1]
                af, bf = a.astype(jnp.float32), b.astype(jnp.float32)
                out = _saturate(jnp.sqrt(af * af + bf * bf), img.dtype)
                bands = bands[:-2] + [(out, oy, ox)]
            elif mode == "tap":
                new = apply_one(s, ph, pw, stride, *bands[tap])
                bands = [crop(*b, ph, pw) for b in bands] + [new]
            else:                        # map over every band
                bands = [apply_one(s, ph, pw, stride, *b) for b in bands]
        outs = []
        for (b, oy, ox), (hk, wk) in zip(bands, sizes):
            assert oy <= 0 and ox <= 0, "chain_ref: halo over-consumed"
            outs.append(b[-oy:-oy + hk, -ox:-ox + wk])
        return tuple(outs)

    def one_image(im):                  # (H, W) or (H, W, C)
        x = _pad_replicate(im, PH, PW)
        if x.ndim == 2:
            return plane_chain(x)
        chans = [plane_chain(x[..., c]) for c in range(x.shape[-1])]
        return tuple(jnp.stack([ch[k] for ch in chans], axis=-1)
                     for k in range(len(chans[0])))

    if img.ndim == 4:
        per = [one_image(img[b]) for b in range(img.shape[0])]
        outs = tuple(jnp.stack([p[k] for p in per]) for k in range(len(per[0])))
    else:
        outs = one_image(img)
    return outs[0] if len(outs) == 1 else outs


def pyramid_ref(img: Array, chains) -> tuple[list, list]:
    """Multi-octave staged oracle for `stencil.chained_launches`: run
    `chain_ref` per link, the LAST output band of every non-final link (the
    next_base terminal strided tap) feeding the next link as its base, with
    per-link origin/scale tracking.

    Every output band is cropped to image origin (chain_ref's contract)
    and strided taps decimate on image-even coordinates, so link k's local
    origin sits exactly at base-image (0, 0) and its pixel (y, x) at base
    coordinates ``(y * scales[k][0], x * scales[k][1])`` — the scale is
    the product of the carry taps' strides walked so far.  Returns
    ``(outs, scales)`` shaped exactly like `stencil.chained_launches` (the
    carry band is removed from every non-final link's tuple)."""
    chains = tuple(tuple(c) for c in chains)
    if not chains:
        raise ValueError("pyramid_ref: need at least one chain")
    outs_all, scales = [], []
    base = img
    sy = sx = 1
    for k, stages in enumerate(chains):
        last = k == len(chains) - 1
        if not last:
            tap = getattr(stages[-1], "tap", None)
            stride = tuple(getattr(stages[-1], "stride", (1, 1)))
            if tap is None or stride == (1, 1):
                raise ValueError(
                    f"pyramid_ref: link {k}'s final stage "
                    f"({stages[-1].op!r}) is not a strided terminal tap — "
                    "non-final links must emit a next_base carry band")
        outs = chain_ref(base, stages)
        if not isinstance(outs, tuple):
            outs = (outs,)
        scales.append((sy, sx))
        if last:
            outs_all.append(outs)
        else:
            outs_all.append(outs[:-1])
            base = outs[-1]
            st = tuple(getattr(stages[-1], "stride", (1, 1)))
            sy, sx = sy * st[0], sx * st[1]
    return outs_all, scales


def bow_assign_ref(desc: Array, centroids: Array) -> tuple[Array, Array]:
    """Nearest-centroid assignment. desc (N, D) f32, centroids (K, D) f32
    -> (assignments (N,) int32, min squared distance (N,) f32)."""
    d2 = (jnp.sum(desc * desc, axis=1, keepdims=True)
          - 2.0 * desc @ centroids.T
          + jnp.sum(centroids * centroids, axis=1)[None, :])
    idx = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return idx, jnp.take_along_axis(d2, idx[:, None], axis=1)[:, 0]


def bow_histogram_ref(assign: Array, K: int, *, normalize: bool = True) -> Array:
    h = jnp.zeros((K,), jnp.float32).at[assign].add(1.0)
    if normalize:
        h = h / jnp.maximum(jnp.sum(h), 1.0)
    return h


def svm_decision_ref(x: Array, w: Array, b: Array) -> Array:
    """Linear multi-class decision values: x (N, D), w (C, D), b (C,)."""
    return jnp.dot(x, w.T, precision=jax.lax.Precision.HIGHEST) + b[None, :]


def bow_hist_ref(descs: Array, valids: Array, centroids: Array, *,
                 normalize: bool = True) -> Array:
    """Staged quantize->histogram oracle for the fused classify head:
    descs (B, N, D), valids (B, N) -> (B, K) word histograms.

    The assignment arithmetic mirrors `kernels.bow._hist_kernel`
    expression-for-expression —  s = -2 d.c + |c|^2  with |d|^2 dropped
    (argmin-invariant), argmin ties to the lowest index — so the fused
    plan's histograms are bit-identical to this staged path (histogram
    counts are order-independent sums of {0, 1} weights).  Contrast
    `bow_assign_ref`, which returns true squared distances and therefore
    may break distance *ties* differently under float rounding.
    """
    B, N, D = descs.shape
    K = centroids.shape[0]
    d = descs.astype(jnp.float32).reshape(B * N, D)
    c = centroids.astype(jnp.float32)
    s = (-2.0 * jnp.dot(d, c.T, precision=jax.lax.Precision.HIGHEST)
         + jnp.sum(c * c, axis=1)[None, :])
    idx = jnp.argmin(s, axis=1).astype(jnp.int32).reshape(B, N)
    w = valids.astype(jnp.float32)
    h = jnp.zeros((B, K), jnp.float32)
    h = h.at[jnp.arange(B)[:, None], idx].add(w)
    if normalize:
        h = h / jnp.maximum(jnp.sum(h, axis=1, keepdims=True), 1e-6)
    return h


def gbdt_leaf_ref(x: Array, feat: Array, thr: Array) -> Array:
    """Oblivious-tree leaf indices: x (B, F), feat/thr (T, depth) ->
    (B, T) int32.  Level l contributes bit 2^l (little-endian in level),
    the same bit layout `kernels.gbdt` packs via its powers-of-two
    matmul — leaf indices are exact in both paths (float compares on
    identical inputs), so fused-vs-ref leaf match is bitwise."""
    xv = x.astype(jnp.float32)[:, feat]                  # (B, T, depth)
    bits = (xv > thr[None].astype(jnp.float32)).astype(jnp.int32)
    pw = (2 ** jnp.arange(feat.shape[1])).astype(jnp.int32)
    return jnp.sum(bits * pw[None, None, :], axis=-1).astype(jnp.int32)


def gbdt_scores_ref(x: Array, feat: Array, thr: Array, leaf: Array,
                    base: Array) -> Array:
    """Staged GBDT ensemble scores: leaf (T, 2^depth, C), base (C,) ->
    (B, C) = base + sum_t leaf[t, leaf_index_t]."""
    lidx = gbdt_leaf_ref(x, feat, thr)                   # (B, T)
    T = leaf.shape[0]
    picked = leaf[jnp.arange(T)[None, :], lidx]          # (B, T, C)
    return base[None, :] + jnp.sum(picked, axis=1)


def attention_ref(q: Array, k: Array, v: Array, *, causal: bool = True) -> Array:
    """q/k/v (B, S, H, hd) -> (B, S, H, hd), fp32 softmax."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    if causal:
        S, T = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
