"""Task `filter`: `CvEngine.filter` serves each frame's output of one
stencil chain, filter2D then erode, on u8 frames: an image in, an image
out.

The filter2D kernel comes from the seed, drawn as OpenCV's perf test
draws it.  The engine has one rung ("streaming") and one bucket, the
frame's own size, so no rung can hide a broken kernel.  Each served image
is held, pixel by pixel, to the plain reference's image of the same
frame.
"""
from __future__ import annotations

import functools

import numpy as np

PLAN = "streaming"
HEAD_STREAM = 0xF117     # seed stream of the kernel, apart from the frames'
REF_BLOCK = 8            # frames per call of the reference on the device

# every distinct image served since the engine was built, by a sample of
# its pixels: the client reads each served image once, and an image equal
# to one served before is kept as that one
_SEEN: dict = {}


def head(cfg: dict, seed: int) -> np.ndarray:
    """The (ksize, ksize) float32 filter2D kernel: uniform in [low, high),
    divided by the absolute value of its sum."""
    k = cfg["kernel"]
    n = k["ksize"]
    draw = np.random.default_rng([HEAD_STREAM, seed]).uniform(
        k["low"], k["high"], (n, n))
    return (draw / abs(draw.sum())).astype(np.float32)


def _radius(cfg: dict) -> int:
    """The erode radius of the configuration's chain, which is filter2D
    at the kernel's size, then erode."""
    f, e = cfg["chain"]
    if (f != {"op": "filter2d", "ksize": cfg["kernel"]["ksize"]}
            or e["op"] != "erode"):
        raise ValueError(f"{cfg['name']}: the filter task serves filter2D "
                         f"then erode, not {cfg['chain']}")
    return e["radius"]


def engine(cfg: dict, kernel, *, mesh=None, max_batch: int):
    from repro.kernels import stencil
    from repro.serve.cv_engine import CvEngine
    _SEEN.clear()
    chain = (stencil.filter_stage(kernel), stencil.erode_stage(_radius(cfg)))
    hw = (cfg["frame"]["height"], cfg["frame"]["width"])
    return CvEngine(None, chain=chain, buckets=(hw,), ladder=(PLAN,),
                    mesh=mesh, max_batch=max_batch)


def submit(eng, frames):
    return eng.filter(frames)


def _same(a: np.ndarray, b: np.ndarray) -> bool:
    if a.shape != b.shape or a.dtype != b.dtype:
        return False
    a, b = a.reshape(-1), b.reshape(-1)
    if a.size % 8 == 0:          # eight pixels a compare
        a, b = a.view(np.uint64), b.view(np.uint64)
    return bool((a == b).all())


def answer(resp) -> dict:
    """The served image, read once: kept as the image served before that
    equals it, if any, so a window keeps each distinct image once.  A new
    image is copied, since the response's image is a view that would keep
    its whole batch alive; so a window holds at most one frame's bytes per
    distinct image.  This runs between submits, inside the timed window."""
    img = np.asarray(resp.image)
    key = (img.shape, img[::61, ::67].tobytes())
    seen = _SEEN.setdefault(key, [])
    for prev in seen:
        if _same(img, prev):
            return {"image": prev}
    img = img.copy()
    seen.append(img)
    return {"image": img}


def _diff(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.abs(a.astype(np.int16) - b.astype(np.int16))


def numbers(cfg: dict, answers: list, refs: dict) -> dict:
    """Over every served pixel, the largest distance from the reference
    (`px_diff_max`), and the worst served frame's share of pixels that
    differ at all (`px_off_share`).  An image served the same way twice
    is compared once."""
    worst, share, seen = 0, 0.0, set()
    for k, a in answers:
        key = (k, id(a["image"]))
        if key in seen:
            continue
        seen.add(key)
        want = refs[k]["image"]
        if a["image"].shape != want.shape:
            return {"px_diff_max": 255, "px_off_share": 1.0}
        d = _diff(a["image"], want)
        worst = max(worst, int(d.max()))
        share = max(share, float((d > 0).mean()))
    return {"px_diff_max": worst, "px_off_share": share}


@functools.lru_cache(maxsize=None)
def _reference_fn(radius: int, dtype: str):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    def plain(frames, kernel):
        """(n, H, W, C) u8 -> (filtered, eroded) u8: filter2D on the
        frames padded by the chain's whole halo, rounded half to even and
        saturated, then the (2r+1)^2 minimum."""
        n, h, w, c = frames.shape
        kh, kw = kernel.shape
        pad_y, pad_x = kh // 2 + radius, kw // 2 + radius
        x = jnp.pad(frames, ((0, 0), (pad_y, pad_y), (pad_x, pad_x),
                             (0, 0)), mode="edge").astype(dt)
        k = kernel.astype(dt)
        fh, fw = h + 2 * radius, w + 2 * radius
        acc = jnp.zeros((n, fh, fw, c), dt)
        for i in range(kh):
            for j in range(kw):
                acc = acc + x[:, i:i + fh, j:j + fw] * k[i, j]
        f = jnp.clip(jnp.round(acc.astype(jnp.float32)), 0, 255)
        f = f.astype(jnp.int32).astype(jnp.uint8)
        out = f[:, :h, :w]
        for i in range(2 * radius + 1):
            for j in range(2 * radius + 1):
                out = jnp.minimum(out, f[:, i:i + h, j:j + w])
        return f[:, radius:radius + h, radius:radius + w], out

    return jax.jit(plain)


def reference(cfg: dict, frames, kernel, dtype: str) -> list:
    """Of each (H, W, C) u8 frame, in `dtype` on the default device,
    REF_BLOCK frames a call: {"image": the chain's output, "filtered": the
    filter2D stage alone, replicate border}."""
    import jax.numpy as jnp
    fn = _reference_fn(_radius(cfg), dtype)
    k = jnp.asarray(kernel)
    frames = np.asarray(frames)
    out = []
    for lo in range(0, len(frames), REF_BLOCK):
        filtered, image = fn(jnp.asarray(frames[lo:lo + REF_BLOCK]), k)
        out.extend({"image": i, "filtered": f}
                   for i, f in zip(np.asarray(image), np.asarray(filtered)))
    return out


def control_answer(ref: dict) -> dict:
    return {"image": ref["image"]}


def stencil_chains(cfg: dict) -> list:
    """One fused chain a frame: its planes in once, its planes out once."""
    f = cfg["frame"]
    c = f["channels"]
    return [(f["height"], f["width"], c, c, f["dtype"])]


def readings(cfg: dict, answers: list, refs: dict, kernel) -> dict:
    """The worst served frame's share of pixels exactly one apart from
    the reference, and the numbers of a stand-in that leaves the erode
    out (the reference's filter2D alone)."""
    off1 = max(float((_diff(a["image"], refs[k]["image"]) == 1).mean())
               for k, a in answers)
    no_erode = [(k, {"image": r["filtered"]}) for k, r in sorted(refs.items())]
    return {"px_off_by_one_max": off1,
            "no_erode": numbers(cfg, no_erode, refs)}
