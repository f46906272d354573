"""Fused stencil-pipeline engine: batched/multi-channel parametrized sweeps
vs the jnp oracles, chain goldens, morph-fold pinning, and the one-launch
guarantee."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autotune import chain_working_set, pick_chain_lmul, plane_block
from repro.core.vector import VectorConfig
from repro.kernels import ops, ref, stencil

SHAPES = [(37, 61), (37, 61, 3), (2, 33, 49, 3)]
DTYPES = [jnp.uint8, jnp.float32]
LMULS = [1, 4]


def _image(rng, shape, dtype):
    if dtype == jnp.uint8:
        return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 100)


def _per_plane(fn, img):
    """Apply a (H, W)/(H, W, C) oracle over an optional batch axis."""
    if img.ndim == 4:
        return jnp.stack([fn(img[b]) for b in range(img.shape[0])])
    return fn(img)


# ---------------------------------------------------------------------------
# public per-op APIs on (H, W), (H, W, C), (B, H, W, C) x dtype x lmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_filter2d_batched(rng, shape, dtype, lmul):
    img = _image(rng, shape, dtype)
    kern = jnp.asarray(rng.standard_normal((3, 3)) * 0.1, jnp.float32)
    out = ops.filter2d(img, kern, vc=VectorConfig(lmul=lmul))
    want = _per_plane(lambda im: ref.filter2d_ref(im, kern), img)
    assert out.shape == img.shape and out.dtype == img.dtype
    if dtype == jnp.uint8:
        assert int(jnp.max(jnp.abs(out.astype(int) - want.astype(int)))) <= 1
    else:
        np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_erode_batched(rng, shape, dtype, lmul):
    img = _image(rng, shape, dtype)
    out = ops.erode(img, 2, vc=VectorConfig(lmul=lmul))
    want = _per_plane(lambda im: ref.erode_ref(im, 2), img)
    assert out.shape == img.shape
    assert (out == want).all()


@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("shape", SHAPES)
def test_sep_filter_batched(rng, shape, lmul):
    img = _image(rng, shape, jnp.uint8)
    k1 = ref.gaussian_kernel1d(5)
    out = ops.sep_filter2d(img, k1, k1, vc=VectorConfig(lmul=lmul))
    want = _per_plane(lambda im: ref.sep_filter2d_ref(im, k1, k1), img)
    assert int(jnp.max(jnp.abs(out.astype(int) - want.astype(int)))) <= 1


@pytest.mark.parametrize("dtype", DTYPES)
def test_dilate_threshold_batched(rng, dtype):
    img = _image(rng, (2, 40, 56, 3), dtype)
    out = ops.dilate(img, 1, vc=VectorConfig(lmul=4))
    want = _per_plane(lambda im: ref.dilate_ref(im, 1), img)
    assert (out == want).all()
    th = ops.threshold(img, 90.0, vc=VectorConfig(lmul=4))
    want = jnp.where(img > jnp.asarray(90.0).astype(img.dtype),
                     jnp.asarray(255.0).astype(img.dtype),
                     jnp.asarray(0).astype(img.dtype))
    assert (th == want).all()


# ---------------------------------------------------------------------------
# chain goldens vs ref.chain_ref (compute-on-extended-domain semantics)
# ---------------------------------------------------------------------------

def _chain3():
    return (stencil.gaussian_stage(5), stencil.erode_stage(1),
            stencil.threshold_stage(100.0))


@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_chain_golden(rng, shape, dtype, lmul):
    img = _image(rng, shape, dtype)
    out = stencil.fused_chain(img, _chain3(), vc=VectorConfig(lmul=lmul))
    want = ref.chain_ref(img, _chain3())
    assert out.shape == img.shape and out.dtype == img.dtype
    if dtype == jnp.uint8:
        assert (out == want).all()
    else:
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)


def test_chain_golden_all_ops(rng):
    """Every stage op in one chain, pinned against the oracle."""
    chain = (stencil.filter_stage(jnp.asarray(rng.standard_normal((3, 3)) * 0.1,
                                              jnp.float32)),
             stencil.grad_stage(),
             stencil.affine_stage(0.5, 10.0),
             stencil.dilate_stage(2),
             stencil.threshold_stage(40.0))
    img = _image(rng, (2, 37, 49, 2), jnp.uint8)
    out = stencil.fused_chain(img, chain, vc=VectorConfig(lmul=1))
    want = ref.chain_ref(img, chain)
    assert (out == want).all()


def test_chain_lmul_invariance(rng):
    """The paper's correctness property, extended to fused chains: block
    width (and plane block) must not change results."""
    img = _image(rng, (3, 50, 70, 3), jnp.uint8)
    outs = [stencil.fused_chain(img, _chain3(), vc=VectorConfig(lmul=l))
            for l in (1, 2, 4, 8)]
    outs.append(stencil.fused_chain(img, _chain3(), vc=None))  # autotuned
    for o in outs[1:]:
        assert (o == outs[0]).all()


# ---------------------------------------------------------------------------
# morph column-reduction fold (erode.py dedup satellite) golden
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op", ["erode", "dilate"])
@pytest.mark.parametrize("r", [1, 2, 3])
def test_morph_fold_golden(rng, op, r):
    """The folded lane-shift loop (TPU lowering) and the reduce_window
    lowering (interpret) both match the oracle exactly."""
    img = _image(rng, (45, 83), jnp.uint8)
    fn = ops.erode if op == "erode" else ops.dilate
    want = (ref.erode_ref if op == "erode" else ref.dilate_ref)(img, r)
    assert (fn(img, r, vc=VectorConfig(lmul=2)) == want).all()
    # the uintr (TPU) lowering, exercised directly on a band with halo
    band = jnp.pad(img, r, mode="edge")
    got = stencil._apply_morph(band, (), (r,), jnp.uint8, op=op, interp=False)
    # full-width output: crop the column halo, rows already consumed
    assert (got[:, r:r + img.shape[1]] == want).all()


# ---------------------------------------------------------------------------
# one-launch guarantee + chain-aware autotune
# ---------------------------------------------------------------------------

def test_chain_is_one_pallas_call(rng):
    batch = _image(rng, (2, 64, 96, 3), jnp.uint8)
    vc = VectorConfig(lmul=4)
    n = stencil.count_pallas_calls(
        lambda x: stencil.fused_chain(x, _chain3(), vc=vc), batch)
    assert n == 1
    # the jitted program of one fused_chain call holds that one launch
    assert stencil.count_pallas_calls(
        jax.jit(lambda x: stencil.fused_chain(x, _chain3(), vc=vc)),
        batch) == 1


def test_chain_working_set_monotone():
    """Longer chains and wider images never pick a larger lmul."""
    one = (stencil.gaussian_stage(5),)
    three = _chain3()
    prev = 99
    for w in (1920, 3840, 7680, 15360):
        l = pick_chain_lmul(three, w).lmul
        assert l <= prev
        assert l <= pick_chain_lmul(one, w).lmul
        prev = l


def test_chain_working_set_fits_budget():
    for w in (1920, 3840, 7680):
        ws = chain_working_set(_chain3(), w)
        vc = pick_chain_lmul(_chain3(), w)
        assert ws.bytes(vc) <= vc.vmem_budget


def test_u8_working_set_covers_what_mosaic_allocates():
    """4K u8 filter2D 5x5 -> 3x3 erode, 16 planes: at 128-row bands Mosaic
    allocates 16.78 MiB of scoped VMEM (16 MiB + 803.5 KiB; TPU v5e
    compile), so the planner has to charge at least that there and pick
    narrower bands: Mosaic holds each filter2D kernel row's slice of the
    band, and the window's DMA block has its rows rounded up to the
    sublane tile."""
    k = np.random.default_rng(0).uniform(-3, 10, (5, 5))
    chain = (stencil.filter_stage((k / abs(k.sum())).astype(np.float32)),
             stencil.erode_stage(1))
    tpu = VectorConfig(interpret=False)
    ws = chain_working_set(chain, 3840, jnp.uint8, streaming=True)
    assert ws.bytes(tpu.with_lmul(4)) >= 16 * 2**20 + 803.5 * 1024
    vc = pick_chain_lmul(chain, 3840, jnp.uint8, base=tpu, streaming=True)
    assert vc.lmul == 2 and ws.bytes(vc) <= vc.vmem_budget
    assert plane_block(chain, 3840, 16, vc, jnp.uint8, streaming=True) == 1


def test_plane_block_budget():
    vc = VectorConfig(lmul=4)
    p = plane_block(_chain3(), 512, 24, vc)
    assert p >= 1 and 24 % p == 0 or p <= 24
    ws = chain_working_set(_chain3(), 512)
    assert p * ws.bytes(vc) <= vc.vmem_budget
    # a plane block never exceeds the plane count
    assert plane_block(_chain3(), 512, 1, vc) == 1


def test_preprocess_bow_single_launch(rng):
    from repro.cv import imgproc
    imgs = _image(rng, (4, 32, 32, 3), jnp.float32)
    out = imgproc.preprocess_bow(imgs)
    assert out.shape == imgs.shape
    assert stencil.count_pallas_calls(imgproc.preprocess_bow, imgs) == 1


# ---------------------------------------------------------------------------
# strided & multi-output stage kinds: goldens vs ref.chain_ref
# ---------------------------------------------------------------------------

DTYPES3 = [jnp.uint8, jnp.float32, jnp.bfloat16]


def _image3(rng, shape, dtype):
    if dtype == jnp.uint8:
        return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 100).astype(dtype)


def _assert_band(out, want, dtype):
    assert out.shape == want.shape and out.dtype == want.dtype
    if out.dtype == jnp.uint8:
        # float-accumulating stages can differ by 1 ulp between the kernel's
        # shift/FMA form and the oracle's slice sums, flipping round() at .5
        # ties — compare u8 with <= 1 (same policy as the per-op filter tests)
        assert int(jnp.max(jnp.abs(out.astype(int) - want.astype(int)))) <= 1
    else:
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 2e-5,
                                   atol=1.0 if dtype == jnp.bfloat16 else 2e-3)


def _assert_chain(img, chain, dtype, lmul):
    out = stencil.fused_chain(img, chain, vc=VectorConfig(lmul=lmul))
    want = ref.chain_ref(img, chain)
    outs = out if isinstance(out, tuple) else (out,)
    wants = want if isinstance(want, tuple) else (want,)
    assert len(outs) == len(wants)
    for o, w in zip(outs, wants):
        _assert_band(o, w, dtype)


@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("dtype", DTYPES3)
@pytest.mark.parametrize("shape", SHAPES)
def test_pyr_down_chain_golden(rng, shape, dtype, lmul):
    """Strided stage mid-chain AND standalone: gauss -> pyrDown."""
    img = _image3(rng, shape, dtype)
    _assert_chain(img, (stencil.pyr_down_stage(),), dtype, lmul)
    _assert_chain(img, (stencil.gaussian_stage(5), stencil.pyr_down_stage()),
                  dtype, lmul)


def test_pyr_down_matches_blur_decimate(rng):
    """Independent pin (not chain_ref): pyrDown == 5-tap separable blur +
    even-coordinate decimation, out = ceil(size/2) (OpenCV geometry)."""
    img = _image3(rng, (37, 61), jnp.uint8)
    out = stencil.fused_chain(img, (stencil.pyr_down_stage(),),
                              vc=VectorConfig(lmul=1))
    k1 = jnp.asarray([1, 4, 6, 4, 1], jnp.float32) / 16
    want = ref.sep_filter2d_ref(img, k1, k1)[::2, ::2]
    assert out.shape == (19, 31)
    assert int(jnp.max(jnp.abs(out.astype(int) - want.astype(int)))) <= 1


@pytest.mark.parametrize("dtype", DTYPES3)
@pytest.mark.parametrize("shape", SHAPES)
def test_resize2_golden(rng, shape, dtype):
    img = _image3(rng, shape, dtype)
    _assert_chain(img, (stencil.resize2_stage(),), dtype, 1)
    # independent pin: floor-half 2x2 mean in f32
    out = stencil.fused_chain(img, (stencil.resize2_stage(),),
                              vc=VectorConfig(lmul=4))
    x = np.asarray(img, np.float32)
    if img.ndim == 2:
        h2, w2 = x.shape[0] // 2, x.shape[1] // 2
        m = x[:h2 * 2, :w2 * 2].reshape(h2, 2, w2, 2).mean((1, 3))
    elif img.ndim == 3:
        h2, w2 = x.shape[0] // 2, x.shape[1] // 2
        m = x[:h2 * 2, :w2 * 2].reshape(h2, 2, w2, 2, -1).mean((1, 3))
    else:
        h2, w2 = x.shape[1] // 2, x.shape[2] // 2
        m = x[:, :h2 * 2, :w2 * 2].reshape(x.shape[0], h2, 2, w2, 2, -1).mean((2, 4))
    if dtype == jnp.uint8:
        np.testing.assert_array_equal(np.asarray(out),
                                      np.clip(np.round(m), 0, 255).astype(np.uint8))
    else:
        np.testing.assert_allclose(np.asarray(out, np.float32), m,
                                   rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6,
                                   atol=1.0 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("dtype", DTYPES3)
def test_box_golden(rng, dtype, r):
    img = _image3(rng, (2, 40, 56, 3), dtype)
    _assert_chain(img, (stencil.box_stage(r),), dtype, 4)
    _assert_chain(img, (stencil.box_stage(r), stencil.threshold_stage(90.0)),
                  dtype, 1)


@pytest.mark.parametrize("dtype", DTYPES3)
@pytest.mark.parametrize("shape", SHAPES)
def test_sobel_pair_golden(rng, shape, dtype):
    """Multi-output stage: sobel emits a widened f32 dx/dy pair."""
    img = _image3(rng, shape, dtype)
    out = stencil.fused_chain(img, (stencil.sobel_stage(),),
                              vc=VectorConfig(lmul=1))
    assert isinstance(out, tuple) and len(out) == 2
    assert all(o.dtype == jnp.float32 for o in out)
    _assert_chain(img, (stencil.sobel_stage(),), dtype, 1)


@pytest.mark.parametrize("lmul", LMULS)
def test_sobel_grad_pair_golden(rng, lmul):
    """grad_mag consumes the Sobel pair (2 bands -> 1, halo 0) but keeps the
    single-band central-difference form when only one band is live."""
    img = _image3(rng, (2, 37, 49, 2), jnp.uint8)
    _assert_chain(img, (stencil.gaussian_stage(3), stencil.sobel_stage(),
                        stencil.grad_stage()), jnp.uint8, lmul)
    # single-band grad_stage unchanged (back-compat)
    _assert_chain(img, (stencil.grad_stage(),), jnp.uint8, lmul)


def test_threshold_fractional_regression(rng):
    """thresh=127.5 on a u8 carrier must bind as x >= 128, not x > 127:
    the comparison runs in f32 (src/repro/kernels/stencil.py bugfix)."""
    img = jnp.arange(256, dtype=jnp.uint8).reshape(16, 16)
    out = stencil.fused_chain(img, (stencil.threshold_stage(127.5),),
                              vc=VectorConfig(lmul=1))
    want = jnp.where(img.astype(jnp.float32) > 127.5,
                     jnp.uint8(255), jnp.uint8(0))
    assert (out == want).all()
    assert int(out.reshape(-1)[127]) == 0 and int(out.reshape(-1)[128]) == 255
    assert (ref.chain_ref(img, (stencil.threshold_stage(127.5),)) == want).all()
    # ops.threshold goes through the same stage
    assert (ops.threshold(img, 127.5) == want).all()


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.float32])
def test_octave_ladder_golden(rng, dtype):
    """Tap ladder + terminal strided tap: every scale and the pyrDown base
    of one fused launch match chain_ref bit-identically (u8)."""
    img = _image3(rng, (50, 70), dtype)
    chain = (stencil.gaussian_stage(5, 1.0),
             stencil.gaussian_stage(5, 0.9, tap=-1),
             stencil.gaussian_stage(7, 1.2, tap=-1),
             stencil.pyr_down_stage(tap=1))
    _assert_chain(img, chain, dtype, 1)
    _assert_chain(img, chain, dtype, 4)


def test_midchain_strided_map_golden(rng):
    """A strided map stage decimates the whole state mid-chain."""
    img = _image3(rng, (2, 37, 61, 3), jnp.uint8)
    _assert_chain(img, (stencil.gaussian_stage(5), stencil.pyr_down_stage(),
                        stencil.erode_stage(1)), jnp.uint8, 1)
    _assert_chain(img, (stencil.resize2_stage(), stencil.gaussian_stage(3)),
                  jnp.uint8, 4)


def test_strided_tap_must_be_terminal(rng):
    img = _image3(rng, (32, 32), jnp.uint8)
    with pytest.raises(ValueError, match="terminal"):
        stencil.fused_chain(img, (stencil.gaussian_stage(3),
                                  stencil.pyr_down_stage(tap=-1),
                                  stencil.erode_stage(1)),
                            vc=VectorConfig(lmul=1))


def test_tap_out_of_range_raises(rng):
    """A tap index outside the live band count must raise, not wrap:
    a silent modulo would tap the wrong ladder band undetectably."""
    img = _image3(rng, (32, 32), jnp.uint8)
    chain = (stencil.gaussian_stage(3), stencil.gaussian_stage(3, tap=3))
    with pytest.raises(ValueError, match="out of range"):
        stencil.fused_chain(img, chain, vc=VectorConfig(lmul=1))
    with pytest.raises(ValueError, match="out of range"):
        ref.chain_ref(img, chain)


# ---------------------------------------------------------------------------
# one-launch guarantees + autotune accounting for the new kinds
# ---------------------------------------------------------------------------

def _octave3():
    """3-scale Gaussian octave + pyrDown (the acceptance chain)."""
    return (stencil.gaussian_stage(7, 1.6),
            stencil.gaussian_stage(5, 1.2, tap=-1),
            stencil.gaussian_stage(5, 1.5, tap=-1),
            stencil.gaussian_stage(7, 1.9, tap=-1),
            stencil.pyr_down_stage(tap=3))


def test_octave_is_one_pallas_call(rng):
    """Acceptance: a 3-scale Gaussian octave + pyrDown lowers to exactly one
    pallas_call and matches ref.chain_ref (u8 within the <= 1 rounding-tie
    tolerance of the float-accumulating ladder)."""
    img = _image3(rng, (64, 96), jnp.uint8)
    vc = VectorConfig(lmul=4)
    n = stencil.count_pallas_calls(
        lambda x: stencil.fused_chain(x, _octave3(), vc=vc), img)
    assert n == 1
    outs = stencil.fused_chain(img, _octave3(), vc=vc)
    wants = ref.chain_ref(img, _octave3())
    assert len(outs) == len(wants) == 5
    for o, w in zip(outs, wants):
        _assert_band(o, w, jnp.uint8)
    assert stencil.count_pallas_calls(
        jax.jit(lambda x: stencil.fused_chain(x, _octave3(), vc=vc)),
        img) == 1


def test_gaussian_octave_single_launch(rng):
    from repro.cv import features
    g = _image3(rng, (64, 80), jnp.float32)
    n = stencil.count_pallas_calls(
        lambda x: features.gaussian_octave(x, n_scales=3), g)
    assert n == 1
    pyr, base = features.gaussian_octave(g, n_scales=3)
    assert pyr.shape == (6, 64, 80)
    assert base.shape == (32, 40)
    # single-octave callers can skip the downsample tap (still one launch)
    pyr2, none = features.gaussian_octave(g, n_scales=3, with_next_base=False)
    assert none is None and pyr2.shape == (6, 64, 80)
    np.testing.assert_allclose(np.asarray(pyr2), np.asarray(pyr), rtol=1e-6)


def test_chain_working_set_counts_bands():
    """A tap ladder keeps every band VMEM-resident: the working set grows
    with band count, so the picked lmul never increases with ladder depth."""
    base = (stencil.gaussian_stage(5),)
    ladder = (stencil.gaussian_stage(5),
              stencil.gaussian_stage(5, tap=-1),
              stencil.gaussian_stage(5, tap=-1),
              stencil.gaussian_stage(5, tap=-1))
    for w in (1920, 3840, 7680):
        ws_base = chain_working_set(base, w).bytes(VectorConfig(lmul=4))
        ws_ladder = chain_working_set(ladder, w).bytes(VectorConfig(lmul=4))
        assert ws_ladder > ws_base
        assert pick_chain_lmul(ladder, w).lmul <= pick_chain_lmul(base, w).lmul
    # strided chains account for pre-decimation geometry: never cheaper to
    # model than the blur alone at the same width
    pyr = (stencil.gaussian_stage(5), stencil.pyr_down_stage())
    for w in (1920, 3840):
        assert (chain_working_set(pyr, w).bytes(VectorConfig(lmul=4))
                > chain_working_set(base, w).bytes(VectorConfig(lmul=4)))


# ---------------------------------------------------------------------------
# gather stages (warp_affine / remap) + pyr_up: goldens vs ref.chain_ref
# ---------------------------------------------------------------------------

def _rot_M(theta=0.05, tx=3.0, ty=-2.0):
    """Small dst->src rotation + translation (inverse-map convention)."""
    c, s = np.cos(theta), np.sin(theta)
    return np.array([[c, -s, tx], [s, c, ty]])


def _jit_ref(img, chain):
    """chain_ref under jit: the gather stages' sample coordinates must be
    computed by the same XLA program kind as the fused kernel, or eager
    rounding of (x*m00 + y*m01 + m02) can differ by an ulp and move a
    bilinear tap (amplified by the local image gradient)."""
    out = jax.jit(lambda x: ref.chain_ref(x, chain))(img)
    return out if isinstance(out, tuple) else (out,)


def _assert_chain_exact(img, chain, lmul=1):
    """Fused output is bit-identical to the jitted oracle (all dtypes)."""
    out = stencil.fused_chain(img, chain, vc=VectorConfig(lmul=lmul))
    outs = out if isinstance(out, tuple) else (out,)
    wants = _jit_ref(img, chain)
    assert len(outs) == len(wants)
    for o, w in zip(outs, wants):
        assert o.shape == w.shape and o.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(o), np.asarray(w))


@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("dtype", DTYPES3)
@pytest.mark.parametrize("shape", SHAPES)
def test_warp_affine_golden(rng, shape, dtype, lmul):
    """Gather stage vs chain_ref: bit-identical on every carrier, batched
    and multichannel (replicate border, bilinear taps)."""
    img = _image3(rng, shape, dtype)
    hw = shape if len(shape) == 2 else shape[-3:-1]
    _assert_chain_exact(img, (stencil.warp_affine_stage(_rot_M(), shape=hw),),
                        lmul)


def test_warp_affine_identity_is_input(rng):
    """Independent pin (not chain_ref): the identity matrix warps every
    pixel to itself — integer sample coordinates, so bilinear returns the
    input exactly."""
    img = _image3(rng, (37, 61), jnp.uint8)
    eye = np.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])
    out = stencil.fused_chain(img, (stencil.warp_affine_stage(eye, shape=(37, 61)),),
                              vc=VectorConfig(lmul=1))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(img))


def test_warp_affine_translate_pin(rng):
    """Integer dst->src translation == a shifted copy with replicate edges."""
    img = _image3(rng, (33, 49), jnp.uint8)
    m = np.array([[1.0, 0.0, 3.0], [0.0, 1.0, -2.0]])   # src = dst + (3, -2)
    out = stencil.fused_chain(img, (stencil.warp_affine_stage(m, shape=(33, 49)),),
                              vc=VectorConfig(lmul=1))
    x = np.asarray(img)
    want = np.pad(x, ((2, 0), (0, 3)), mode="edge")[:33, 3:]
    np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("dtype", DTYPES3)
@pytest.mark.parametrize("shape", SHAPES)
def test_remap_golden(rng, shape, dtype):
    """Precomputed-map gather vs chain_ref: the (H, W) map planes enter as
    extra chain inputs; bound auto-computed from the maps."""
    img = _image3(rng, shape, dtype)
    h, w = (shape[-3], shape[-2]) if len(shape) > 2 else shape
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    map_y = yy + 1.5 * np.sin(xx / 7.0)
    map_x = xx + 1.2 * np.cos(yy / 5.0)
    _assert_chain_exact(img, (stencil.remap_stage(map_x, map_y),), 1)


def test_remap_identity_is_input(rng):
    img = _image3(rng, (40, 56), jnp.float32)
    yy, xx = np.mgrid[0:40, 0:56].astype(np.float32)
    out = stencil.fused_chain(img, (stencil.remap_stage(xx, yy),),
                              vc=VectorConfig(lmul=4))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(img))


def test_gather_midchain_golden(rng):
    """Gather stages compose with stencil stages on both sides; u8 stays
    bit-exact (the ulp-tie hazard is fenced by global-coordinate frac)."""
    img = _image3(rng, (2, 37, 61, 2), jnp.uint8)
    h, w = 37, 61
    yy, xx = np.mgrid[0:h, 0:w].astype(np.float32)
    chain = (stencil.remap_stage(xx + np.cos(yy / 3.0), yy + np.sin(xx / 4.0),
                                 extend=(1, 1)),
             stencil.erode_stage(1))
    _assert_chain_exact(img, chain, 1)
    chain2 = (stencil.gaussian_stage(3),
              stencil.warp_affine_stage(_rot_M(0.03), shape=(h, w)),)
    _assert_chain_exact(img, chain2, 4)


def test_warp_ladder_chain_golden(rng):
    """The align_and_detect shape: warp -> incremental Gaussian tap ladder,
    bound extended by the ladder halo.  u8 bit-exact; f32 within the
    standard chain tolerance (coordinate-ulp x local gradient)."""
    ladder = (stencil.gaussian_stage(5, 1.6),
              stencil.gaussian_stage(5, 1.2, tap=-1),
              stencil.gaussian_stage(5, 1.4, tap=-1))
    ey, ex = stencil.chain_halo(ladder)
    chain = (stencil.warp_affine_stage(_rot_M(), shape=(37, 61),
                                       extend=(ey, ex)),) + ladder
    _assert_chain_exact(_image3(rng, (37, 61), jnp.uint8), chain, 1)
    imgf = _image3(rng, (37, 61), jnp.float32)
    out = stencil.fused_chain(imgf, chain, vc=VectorConfig(lmul=4))
    for o, w in zip(out, _jit_ref(imgf, chain)):
        np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                   rtol=1e-5, atol=1e-3)


def test_warp_bound_too_small_raises(rng):
    """A declared displacement bound that undershoots the fused window's
    halo-ring evaluation must raise, not silently clamp the gathers."""
    img = _image3(rng, (37, 61), jnp.uint8)
    with pytest.raises(ValueError, match="displacement"):
        stencil.fused_chain(
            img, (stencil.warp_affine_stage(_rot_M(), bound=(0.1, 0.1)),
                  stencil.gaussian_stage(5)), vc=VectorConfig(lmul=1))


def test_remap_needs_extend_for_downstream(rng):
    """remap's auto-bound covers in-image lookups only: a downstream halo
    consumer needs extend=, and the compiler enforces it."""
    img = _image3(rng, (37, 61), jnp.uint8)
    yy, xx = np.mgrid[0:37, 0:61].astype(np.float32)
    chain = (stencil.remap_stage(xx, yy), stencil.erode_stage(2))
    with pytest.raises(ValueError, match="displacement"):
        stencil.fused_chain(img, chain, vc=VectorConfig(lmul=1))
    ok = (stencil.remap_stage(xx, yy, extend=(2, 2)), stencil.erode_stage(2))
    _assert_chain_exact(img, ok, 1)


@pytest.mark.parametrize("lmul", LMULS)
@pytest.mark.parametrize("dtype", DTYPES3)
@pytest.mark.parametrize("shape", SHAPES)
def test_pyr_up_golden(rng, shape, dtype, lmul):
    """The first fractional-stride stage: standalone (bit-exact) and after
    a blur (standard chain tolerance: the Gaussian's FMA-vs-sum f32 ulp)."""
    img = _image3(rng, shape, dtype)
    _assert_chain_exact(img, (stencil.pyr_up_stage(),), lmul)
    _assert_chain(img, (stencil.gaussian_stage(3), stencil.pyr_up_stage()),
                  dtype, lmul)


def test_pyr_up_matches_zero_insert_conv(rng):
    """Independent pin (not chain_ref): pyrUp == zero-insert upsample
    convolved with 4x the 5-tap pyramid kernel (OpenCV definition),
    replicate-extended at the source resolution."""
    img = _image3(rng, (19, 31), jnp.float32)
    out = stencil.fused_chain(img, (stencil.pyr_up_stage(),),
                              vc=VectorConfig(lmul=1))
    x = np.asarray(img, np.float64)
    xp = np.pad(x, 2, mode="edge")                      # source-res replicate
    up = np.zeros((2 * xp.shape[0], 2 * xp.shape[1]))
    up[0::2, 0::2] = xp
    k1 = np.array([1.0, 4.0, 6.0, 4.0, 1.0]) / 16.0
    k = 4.0 * np.outer(k1, k1)
    conv = np.zeros_like(up)
    upp = np.pad(up, 2)
    for i in range(5):
        for j in range(5):
            conv += k[i, j] * upp[i:i + up.shape[0], j:j + up.shape[1]]
    want = conv[4:4 + 38, 4:4 + 62]                     # drop the pad ring
    assert out.shape == (38, 62)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=1e-5, atol=1e-4)


def test_pyr_up_down_roundtrip(rng):
    """pyrUp o pyrDown restores the original geometry (even dims) and, on a
    smooth image, the original values to low error — fused as one chain AND
    as two single-op launches (same result)."""
    yy, xx = np.mgrid[0:48, 0:64].astype(np.float32)
    smooth = jnp.asarray(100.0 + 50.0 * np.sin(xx / 9.0) * np.cos(yy / 11.0))
    chain = (stencil.pyr_down_stage(), stencil.pyr_up_stage())
    out = stencil.fused_chain(smooth, chain, vc=VectorConfig(lmul=1))
    assert out.shape == (48, 64)
    _assert_chain_exact(smooth, chain, 1)
    staged = ops.pyr_up(ops.pyr_down(smooth, vc=VectorConfig(lmul=1)),
                        vc=VectorConfig(lmul=1))
    # interior: fused differs from staged only in the halo ring
    np.testing.assert_allclose(np.asarray(out)[4:-4, 4:-4],
                               np.asarray(staged)[4:-4, 4:-4], rtol=1e-6)
    err = np.max(np.abs(np.asarray(out)[4:-4, 4:-4]
                        - np.asarray(smooth)[4:-4, 4:-4]))
    assert err < 2.0        # smooth signal survives the down/up round trip


def test_pyr_up_rejects_tap(rng):
    with pytest.raises(ValueError, match="tap"):
        ref.chain_ref(_image3(rng, (32, 32), jnp.uint8),
                      (stencil.Stage("pyr_up", tap=0),))
    with pytest.raises(ValueError, match="tap"):
        stencil.fused_chain(_image3(rng, (32, 32), jnp.uint8),
                            (stencil.gaussian_stage(3),
                             stencil.Stage("pyr_up", tap=0)),
                            vc=VectorConfig(lmul=1))


def test_warp_ladder_is_one_pallas_call(rng):
    """Acceptance: the warp -> Gaussian ladder chain lowers to exactly ONE
    pallas_call (the geometric transform no longer breaks the fusion)."""
    ladder = (stencil.gaussian_stage(5, 1.6),
              stencil.gaussian_stage(5, 1.2, tap=-1),
              stencil.gaussian_stage(5, 1.4, tap=-1))
    ey, ex = stencil.chain_halo(ladder)
    chain = (stencil.warp_affine_stage(_rot_M(), shape=(64, 96),
                                       extend=(ey, ex)),) + ladder
    img = _image3(rng, (64, 96), jnp.float32)
    vc = VectorConfig(lmul=4)
    n = stencil.count_pallas_calls(
        lambda x: stencil.fused_chain(x, chain, vc=vc), img)
    assert n == 1
    assert stencil.count_pallas_calls(
        jax.jit(lambda x: stencil.fused_chain(x, chain, vc=vc)), img) == 1


def test_small_plane_falls_back_to_ref(rng):
    """Planes smaller than the accumulated halo fall back to chain_ref
    (identical semantics, zero Pallas launches) instead of running a
    pad-dominated fused window."""
    chain = (stencil.gaussian_stage(7, 1.6),
             stencil.gaussian_stage(7, 1.9, tap=-1),
             stencil.gaussian_stage(7, 2.3, tap=-1),
             stencil.pyr_down_stage(tap=2))      # accumulated halo 11 > 8
    ph, pw = stencil.chain_halo(chain)
    img = _image3(rng, (8, 8), jnp.uint8)
    assert img.shape[0] <= ph and img.shape[1] <= pw
    n = stencil.count_pallas_calls(
        lambda x: stencil.fused_chain(x, chain, vc=VectorConfig(lmul=1))[0], img)
    outs = stencil.fused_chain(img, chain, vc=VectorConfig(lmul=1))
    assert n == 0
    wants = ref.chain_ref(img, chain)
    for o, w in zip(outs, wants):
        assert o.shape == w.shape and o.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(o), np.asarray(w))
    # batched small planes take the same fallback
    imgb = _image3(rng, (2, 8, 8, 3), jnp.uint8)
    outs_b = stencil.fused_chain(imgb, chain, vc=VectorConfig(lmul=1))
    wants_b = ref.chain_ref(imgb, chain)
    for o, w in zip(outs_b, wants_b):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(w))


def test_gather_and_pyr_up_working_set():
    """Autotune accounting: remap charges its two full-size f32 map planes;
    pyr_up charges the doubled output width."""
    yy, xx = np.mgrid[0:256, 0:512].astype(np.float32)
    base = (stencil.gaussian_stage(5),)
    rm = (stencil.remap_stage(xx, yy),)
    up = (stencil.pyr_up_stage(),)
    vc = VectorConfig(lmul=4)
    ws_base = chain_working_set(base, 512).bytes(vc)
    ws_rm = chain_working_set(rm, 512).bytes(vc)
    assert ws_rm - ws_base >= 2 * 256 * 512 * 4      # the two map planes
    assert (chain_working_set(up, 512).bytes(vc)
            > chain_working_set((stencil.gaussian_stage(3),), 512).bytes(vc))
    # the lmul rule stays monotone through the new kinds
    for w in (1920, 3840):
        assert pick_chain_lmul(up, w).lmul <= pick_chain_lmul(base, w).lmul


def test_count_pallas_calls_compat():
    """count_pallas_calls walks jaxprs through jax.extend.core's public
    ClosedJaxpr/Jaxpr types — and sees through nested jits."""
    img = jnp.zeros((32, 32), jnp.uint8)
    inner = jax.jit(lambda x: stencil.fused_chain(
        x, (stencil.gaussian_stage(3),), vc=VectorConfig(lmul=1)))
    assert stencil.count_pallas_calls(lambda x: inner(x) + inner(x), img) == 2
