"""Plain reference of the served pipelines, written from their published
description and the configuration file alone (it imports nothing of the
program under test and takes none of its tables).

Per frame: the BoW preprocess chain (Gaussian 5x5 -> erosion r=1 ->
central-difference gradient magnitude, per channel, on one replicate pad
of the input by the chain's whole halo, every stage in valid mode), BT.601
gray normalised by its maximum, one SIFT octave (base blur and the
incremental Gaussian taps, again on one replicate pad), the 3x3x3 DoG
extrema with contrast and edge tests, the top `max_kp` responses, and
4x4x8 gradient-histogram descriptors; for a classifier, the nearest-word
histogram and the one-vs-rest linear scores.

`dtype` is the precision every plane and descriptor is computed in:
float32 is the configuration's; bfloat16 is the control, the step below.
"""
from __future__ import annotations

import functools
import math

import numpy as np


def gaussian_kernel(ksize: int, sigma: float | None) -> np.ndarray:
    """OpenCV getGaussianKernel, in float64 (sigma <= 0: OpenCV's default
    0.3 * ((ksize - 1) / 2 - 1) + 0.8)."""
    if sigma is None or sigma <= 0:
        sigma = 0.3 * ((ksize - 1) * 0.5 - 1) + 0.8
    x = np.arange(ksize, dtype=np.float64) - (ksize - 1) / 2
    k = np.exp(-(x * x) / (2.0 * sigma * sigma))
    return k / k.sum()


def _sep_valid(x, k):
    """Separable correlation in valid mode: row pass, then column pass."""
    n = len(k)
    h, w = x.shape[0] - (n - 1), x.shape[1] - (n - 1)
    row = sum(k[j] * x[:, j:j + w] for j in range(n))
    return sum(k[i] * row[i:i + h] for i in range(n))


def _erode_valid(x, r):
    import jax.numpy as jnp
    h, w = x.shape[0] - 2 * r, x.shape[1] - 2 * r
    out = x[0:h, 0:w]
    for i in range(2 * r + 1):
        for j in range(2 * r + 1):
            out = jnp.minimum(out, x[i:i + h, j:j + w])
    return out


def _grad_valid(x):
    import jax.numpy as jnp
    h, w = x.shape[0] - 2, x.shape[1] - 2
    dy = (x[2:2 + h, 1:1 + w] - x[0:h, 1:1 + w]) * 0.5
    dx = (x[1:1 + h, 2:2 + w] - x[1:1 + h, 0:w]) * 0.5
    return jnp.sqrt(dx * dx + dy * dy)


def _preprocess(frame, cfg, dt):
    """(H, W, C) u8 -> (H, W, C) gradient magnitude of the eroded blur."""
    import jax.numpy as jnp
    p = cfg["preprocess"]
    kg = jnp.asarray(gaussian_kernel(p["gaussian_ksize"], p["gaussian_sigma"]),
                     dt)
    r = p["erode_radius"]
    halo = p["gaussian_ksize"] // 2 + r + 1
    x = jnp.pad(frame.astype(dt), ((halo, halo), (halo, halo), (0, 0)),
                mode="edge")
    chans = []
    for c in range(x.shape[-1]):
        b = _sep_valid(x[..., c], kg)
        b = _erode_valid(b, r)
        chans.append(_grad_valid(b))
    return jnp.stack(chans, axis=-1)


def _gray(img, cfg, dt):
    import jax.numpy as jnp
    wr, wg, wb = (jnp.asarray(v, dt) for v in cfg["gray_weights"])
    g = img[..., 0] * wr + img[..., 1] * wg + img[..., 2] * wb
    return g / jnp.maximum(jnp.max(g), jnp.asarray(1e-6, dt))


def _octave(g, cfg, dt):
    """(H, W) -> (S + 3, H, W): each scale blurs the previous one."""
    import jax.numpy as jnp
    taps = cfg["octave_taps"]
    halo = sum(k // 2 for k, _ in taps)
    H, W = g.shape
    cur = jnp.pad(g, halo, mode="edge")
    bands, left = [], halo
    for ksize, sigma in taps:
        cur = _sep_valid(cur, jnp.asarray(gaussian_kernel(ksize, sigma), dt))
        left -= ksize // 2
        bands.append(cur[left:left + H, left:left + W])
    return jnp.stack(bands)


def _shift(a, di, dj):
    """a[:, i - di, j - dj] with the image edge replicated."""
    import jax.numpy as jnp
    H, W = a.shape[1:]
    ap = jnp.pad(a, ((0, 0), (1, 1), (1, 1)), mode="edge")
    return ap[:, 1 - di:1 - di + H, 1 - dj:1 - dj + W]


def _keypoints(pyr, cfg, dt):
    """DoG extrema over 3x3x3 neighbourhoods, contrast and Harris edge
    tests, border mask; the top max_kp by |DoG| (0 = no keypoint)."""
    import jax
    import jax.numpy as jnp
    S, H, W = pyr.shape[0] - 3, pyr.shape[1], pyr.shape[2]
    dogs = pyr[1:] - pyr[:-1]
    mid = dogs[1:-1]
    nmax = jnp.full_like(mid, -jnp.inf)
    nmin = jnp.full_like(mid, jnp.inf)
    for ds in (-1, 0, 1):
        lvl = dogs[1 + ds:1 + ds + S]
        for di in (-1, 0, 1):
            for dj in (-1, 0, 1):
                if ds == di == dj == 0:
                    continue
                v = _shift(lvl, di, dj)
                nmax = jnp.maximum(nmax, v)
                nmin = jnp.minimum(nmin, v)
    ct = jnp.asarray(cfg["contrast_thresh"], dt)
    ext = ((mid > nmax) & (mid > ct)) | ((mid < nmin) & (mid < -ct))
    dxx = _shift(mid, 0, 1) + _shift(mid, 0, -1) - 2 * mid
    dyy = _shift(mid, 1, 0) + _shift(mid, -1, 0) - 2 * mid
    dxy = 0.25 * (_shift(mid, 1, 1) + _shift(mid, -1, -1)
                  - _shift(mid, 1, -1) - _shift(mid, -1, 1))
    tr, det = dxx + dyy, dxx * dyy - dxy * dxy
    r = cfg["edge_thresh"]
    edge_ok = (det > 0) & (tr * tr * r < (r + 1) ** 2 * det)
    b = cfg["border"]
    ii = jnp.arange(H)[None, :, None]
    jj = jnp.arange(W)[None, None, :]
    inside = (ii >= b) & (ii < H - b) & (jj >= b) & (jj < W - b)
    score = jnp.where(ext & edge_ok & inside, jnp.abs(mid), 0)
    resp, idx = jax.lax.top_k(score.reshape(-1), cfg["max_kp"])
    rem = idx % (H * W)
    return rem % W, rem // W, resp > 0


def _descriptors(g, xs, ys, valid, cfg, dt):
    """Orientation-normalised 4x4 cells x 8 bins around each keypoint,
    L2-normalised, clamped, renormalised; zeros where not valid."""
    import jax
    import jax.numpy as jnp
    d = cfg["descriptor"]
    patch, cells, bins, obins = (d["patch"], d["cells"], d["bins"],
                                 d["orientation_bins"])
    clamp = jnp.asarray(d["clamp"], dt)
    H, W = g.shape
    gx = jnp.pad(g[:, 2:] - g[:, :-2], ((0, 0), (1, 1))) * 0.5
    gy = jnp.pad(g[2:, :] - g[:-2, :], ((1, 1), (0, 0))) * 0.5
    mag = jnp.sqrt(gx * gx + gy * gy)
    ang = jnp.arctan2(gy, gx)
    pi = math.pi
    half = patch // 2
    cell = jnp.arange(patch) // (patch // cells)
    cell_id = cell[:, None] * cells + cell[None, :]
    eps = jnp.asarray(1e-6, dt)

    def one(x, y, ok):
        x0 = jnp.clip(x - half, 0, W - patch)
        y0 = jnp.clip(y - half, 0, H - patch)
        m = jax.lax.dynamic_slice(mag, (y0, x0), (patch, patch)).reshape(-1)
        a = jax.lax.dynamic_slice(ang, (y0, x0), (patch, patch))
        ob = jnp.floor((a + pi) / (2 * pi) * obins).astype(jnp.int32) % obins
        oh = jnp.zeros((obins,), dt).at[ob.reshape(-1)].add(m)
        dom = jnp.argmax(oh).astype(dt) * (2 * pi / obins) - pi
        rel = (a - dom + 3 * pi) % (2 * pi)
        bn = jnp.floor(rel / (2 * pi) * bins).astype(jnp.int32) % bins
        flat = (cell_id * bins + bn).reshape(-1)
        v = jnp.zeros((cells * cells * bins,), dt).at[flat].add(m)
        v = v / jnp.maximum(jnp.linalg.norm(v), eps)
        v = jnp.minimum(v, clamp)
        v = v / jnp.maximum(jnp.linalg.norm(v), eps)
        return jnp.where(ok, v, 0)

    return jax.vmap(one)(xs, ys, valid)


def _extract(frame, cfg, dt):
    g = _gray(_preprocess(frame, cfg, dt), cfg, dt)
    xs, ys, valid = _keypoints(_octave(g, cfg, dt), cfg, dt)
    return _descriptors(g, xs, ys, valid, cfg, dt), valid


def bow_scores(desc, valid, centroids, w, b, dt):
    """Nearest word (|d - c|^2 without the per-descriptor |d|^2) per valid
    descriptor, the normalised histogram, and h @ w.T + b."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    c = centroids.astype(dt)
    dist = -2 * jnp.dot(desc.astype(dt), c.T, precision=hi) \
        + jnp.sum(c * c, axis=1)[None, :]
    word = jnp.argmin(dist, axis=1)
    h = jnp.zeros((c.shape[0],), dt).at[word].add(valid.astype(dt))
    h = h / jnp.maximum(jnp.sum(h), jnp.asarray(1e-6, dt))
    return jnp.dot(h, w.astype(dt).T, precision=hi) + b.astype(dt)


@functools.lru_cache(maxsize=None)
def _compiled(key, dtype_name: str, with_head: bool):
    """One jitted per-frame program per (configuration, precision)."""
    import jax
    import jax.numpy as jnp
    cfg = _FROZEN[key]
    dt = jnp.dtype(dtype_name)

    def run(frame, head):
        desc, valid = _extract(frame, cfg, dt)
        out = {"desc": desc.astype(jnp.float32), "valid": valid}
        if with_head:
            out["scores"] = bow_scores(desc, valid, *head, dt).astype(
                jnp.float32)
        return out

    return jax.jit(run)


_FROZEN: dict = {}


def reference(cfg: dict, frames, head=None, dtype: str = "float32") -> list:
    """Per frame of `frames` ((N, H, W, C) u8, host or device): a dict of
    host arrays {"desc", "valid"[, "scores"]}.  `head` is the classifier
    (centroids, w, b), or None for descriptors alone.  Frames run one at a
    time, so the reference fits beside nothing else on the device."""
    import json
    import jax
    key = json.dumps(cfg, sort_keys=True)
    _FROZEN[key] = cfg
    fn = _compiled(key, dtype, head is not None)
    outs = []
    for f in frames:
        o = jax.device_get(fn(f, head if head is not None else ()))
        outs.append({k: np.asarray(v) for k, v in o.items()})
    return outs
