"""CV pipeline: SIFT-lite determinism, BoW histograms, SVM, end-to-end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cv import bow, features, imgproc, pipeline, svm
from repro.data.synthetic import ImageStream


@pytest.fixture(scope="module")
def imgs():
    return ImageStream().batch(40, split="train")


def test_resize_half_preserves_dtype():
    """Regression (src/repro/cv/imgproc.py): the pyramid downsample must not
    silently promote u8 to float32 — round+clip back to the carrier."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (33, 47), dtype=np.uint8))
    y = imgproc.resize_half(x)
    assert y.dtype == jnp.uint8 and y.shape == (16, 23)
    m = np.asarray(x)[:32, :46].astype(np.float32).reshape(16, 2, 23, 2).mean((1, 3))
    np.testing.assert_array_equal(np.asarray(y),
                                  np.clip(np.round(m), 0, 255).astype(np.uint8))
    xf = x.astype(jnp.float32)
    assert imgproc.resize_half(xf).dtype == jnp.float32  # widening is explicit


def test_sift_octave_is_one_launch():
    """The SIFT scale ladder + next-octave downsample is ONE fused launch."""
    from repro.kernels import stencil
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((48, 64)).astype(np.float32))
    pyr, base = features.gaussian_octave(g, n_scales=4)
    assert stencil.count_pallas_calls(
        lambda x: features.gaussian_octave(x, n_scales=4), g) == 1
    assert pyr.shape == (7, 48, 64) and base.shape == (24, 32)
    # scales blur monotonically (total variation shrinks up the ladder)
    tv = [float(jnp.abs(jnp.diff(pyr[i], axis=1)).mean()) for i in range(7)]
    assert all(a >= b for a, b in zip(tv, tv[1:]))


def test_detect_keypoints_border_clamp_regression():
    """Regression (src/repro/cv/features.py): the 3x3x3 extremum shifts used
    jnp.roll, so border pixels compared against wrapped-around values from
    the opposite image edge — a bright edge feature's extremum verdict
    depended on what sat on the OTHER side of the image.  The edge-clamped
    (replicate) shifts make border verdicts local: a border pixel's
    neighborhood now includes its own replicate, so verdicts there are
    conservative and invariant to opposite-edge content."""
    from repro.cv.features import gaussian_octave
    H = W = 48
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    base = np.full((H, W), 0.1, np.float32)
    base += 1.0 * np.exp(-(yy ** 2 + (xx - 24) ** 2) / (2 * 2.3 ** 2))

    # the old roll-based neighborhood called this top-edge blob an extremum
    # at (0, 24) — against values wrapped from the bottom edge
    g = jnp.asarray(base) / base.max()
    pyr = np.asarray(gaussian_octave(g, n_scales=4, with_next_base=False)[0])
    dogs = pyr[1:] - pyr[:-1]
    mid = dogs[1:-1]
    nmin = np.full_like(mid, np.inf)
    for ds in (-1, 0, 1):
        lvl = dogs[1 + ds: dogs.shape[0] - 1 + ds]
        for di in (-1, 0, 1):
            for dj in (-1, 0, 1):
                if ds == di == dj == 0:
                    continue
                nmin = np.minimum(nmin, np.roll(np.roll(lvl, di, 1), dj, 2))
    roll_verdict = (mid < nmin) & (mid < -0.005)
    assert roll_verdict[:, 0, 24].any()          # the buggy verdict

    def detected(img):
        det = features.detect_keypoints(jnp.asarray(img), max_kp=8,
                                        border=0, contrast_thresh=0.005)
        xy, ok = np.asarray(det["xy"]), np.asarray(det["valid"])
        return sorted((int(xy[i, 0]), int(xy[i, 1]))
                      for i in range(len(ok)) if ok[i])

    # post-fix: no keypoint from the wrap-dependent border verdict...
    assert (24, 0) not in detected(base)
    # ...and detection is invariant to opposite-edge content (under roll,
    # a bright bottom band flipped the (0, 24) verdict back and forth)
    variant = base.copy()
    # below the blob's peak, so the detect-time max-normalization is shared
    variant += 0.8 * np.exp(-((yy - (H - 1)) ** 2) / (2 * 2.0 ** 2))
    assert variant.max() == base.max()
    v = variant / variant.max()
    b = base / base.max()
    pyr_b = np.asarray(gaussian_octave(jnp.asarray(b), n_scales=4,
                                       with_next_base=False)[0])
    pyr_v = np.asarray(gaussian_octave(jnp.asarray(v), n_scales=4,
                                       with_next_base=False)[0])
    # top rows of the pyramids agree, so any keypoint difference up there
    # could only come from wraparound — there must be none
    np.testing.assert_allclose(pyr_b[:, :8], pyr_v[:, :8], atol=1e-5)
    top_b = [p for p in detected(b) if p[1] < 8]
    top_v = [p for p in detected(v) if p[1] < 8]
    assert top_b == top_v


def test_gaussian_octave_uncapped_ladder_golden():
    """Regression (src/repro/cv/features.py): ksz() used to clamp EVERY tap
    to max_ksize=15, truncating the large-sigma-delta top-of-ladder taps
    and biasing the DoG; taps are now sized per-delta at full width.  Pin
    the whole octave — top band included — against an un-capped chain_ref
    golden, and show the old truncated ladder really differed."""
    import math
    from repro.kernels import ref, stencil
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.standard_normal((48, 64)).astype(np.float32))
    n_scales, sigma0 = 3, 1.6          # top delta ~ 3.1 -> ksize 19 > 15
    pyr, _ = features.gaussian_octave(g, n_scales=n_scales, sigma0=sigma0,
                                      with_next_base=False)

    sigmas = [sigma0 * 2 ** (i / n_scales) for i in range(n_scales + 3)]
    deltas = [sigmas[0]] + [math.sqrt(s * s - p * p)
                            for p, s in zip(sigmas, sigmas[1:])]
    assert max(2 * round(3 * d) + 1 for d in deltas) > 15   # cap would bind

    def ladder(cap):
        ks = [max(3, 2 * round(3 * d) + 1) for d in deltas]
        if cap:
            ks = [min(k, cap) for k in ks]
        return tuple(stencil.gaussian_stage(k, d, tap=None if i == 0 else -1)
                     for i, (k, d) in enumerate(zip(ks, deltas)))

    want = ref.chain_ref(g, ladder(cap=None))
    for band, w in zip(pyr, want):
        np.testing.assert_allclose(np.asarray(band), np.asarray(w),
                                   rtol=1e-5, atol=1e-4)
    # the truncated ladder is measurably different at the top band
    want_capped = ref.chain_ref(g, ladder(cap=15))
    assert float(jnp.max(jnp.abs(want[-1] - want_capped[-1]))) > 1e-3


def test_align_and_detect_one_launch_and_alignment():
    """features.align_and_detect: warp -> Gaussian ladder -> DoG lowers to
    exactly ONE pallas_call, identity-M matches detect_keypoints, and a
    translation M moves the detected feature by the inverse offset."""
    from repro.kernels import stencil
    H, W = 64, 80
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    img = np.full((H, W), 0.1, np.float32)
    img += 1.0 * np.exp(-((yy - 30) ** 2 + (xx - 40) ** 2) / (2 * 2.3 ** 2))
    img = jnp.asarray(img)

    eye = np.array([[1.0, 0.0, 0.0], [0.0, 1.0, 0.0]])
    n = stencil.count_pallas_calls(
        lambda x: features.align_and_detect(x, eye, max_kp=4)["resp"], img)
    assert n == 1

    det = features.detect_keypoints(img, max_kp=4)
    ali = features.align_and_detect(img, eye, max_kp=4)
    np.testing.assert_array_equal(np.asarray(det["xy"]), np.asarray(ali["xy"]))
    assert bool(np.asarray(det["valid"])[0])

    # inverse map src = dst + (3, 5): the feature at src (40, 30) must
    # appear at dst (37, 25) on the aligned image
    m = np.array([[1.0, 0.0, 3.0], [0.0, 1.0, 5.0]])
    moved = features.align_and_detect(img, m, max_kp=4)
    xy, ok = np.asarray(moved["xy"]), np.asarray(moved["valid"])
    assert ok[0] and (int(xy[0, 0]), int(xy[0, 1])) == (37, 25)
    # the warped gray rides along as band 0 of the same launch
    assert moved["gray"].shape == (H, W)


def test_pyr_up_roundtrip_cv():
    """imgproc.pyr_up o imgproc.pyr_down keeps geometry and dtype."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 256, (40, 56), dtype=np.uint8))
    y = imgproc.pyr_up(imgproc.pyr_down(x))
    assert y.shape == x.shape and y.dtype == x.dtype
    xf = x.astype(jnp.float32)
    assert imgproc.pyr_up(xf).dtype == jnp.float32


def test_sift_shapes(imgs):
    x, _ = imgs
    out = features.sift(x[0].astype(jnp.float32), max_kp=16)
    assert out["desc"].shape == (16, 128)
    assert out["valid"].dtype == jnp.bool_
    # descriptors are L2-bounded (SIFT clamp + renorm)
    norms = jnp.linalg.norm(out["desc"], axis=1)
    assert float(jnp.max(norms)) < 1.01


def test_histogram_normalized(imgs):
    x, _ = imgs
    key = jax.random.key(0)
    desc = jax.random.normal(key, (4, 32, 128))
    valid = jnp.ones((4, 32), bool)
    cents = jax.random.normal(key, (16, 128))
    h = bow.batch_histograms(desc, valid, cents, use_kernel=False)
    np.testing.assert_allclose(np.asarray(jnp.sum(h, axis=1)), 1.0, rtol=1e-5)


def test_svm_separates():
    key = jax.random.key(0)
    x0 = jax.random.normal(key, (50, 8)) + jnp.asarray([3.0] + [0] * 7)
    x1 = jax.random.normal(jax.random.key(1), (50, 8)) - jnp.asarray([3.0] + [0] * 7)
    x = jnp.concatenate([x0, x1])
    y = jnp.concatenate([jnp.zeros(50, jnp.int32), jnp.ones(50, jnp.int32)])
    model = svm.svm_train(x, y, n_classes=2, steps=200)
    acc = float(jnp.mean((svm.svm_predict(model, x) == y)))
    assert acc > 0.95


def test_pipeline_beats_chance(imgs):
    x, y = imgs
    model = pipeline.train(jax.random.key(0), x, y, dict_size=32, max_kp=8)
    xte, yte = ImageStream().batch(30, split="test")
    acc = pipeline.accuracy(model, xte, yte, max_kp=8)
    assert acc > 0.15   # 10 classes, chance 0.1


def test_kmeans_all_zero_weights_keeps_finite_centroids():
    # regression: every cluster empty (all-zero weight vector) must keep
    # the seeded init unchanged — no NaN/Inf from the empty-cluster mean
    # (the counts > 0 guard in bow.kmeans); seeding itself must survive
    # the degenerate weight distribution via the uniform fallback
    key = jax.random.key(3)
    desc = jax.random.normal(key, (64, 16))
    cents = bow.kmeans(key, desc, jnp.zeros(64), k=8, iters=5)
    assert bool(jnp.all(jnp.isfinite(cents)))
    # zero updates: the centroids ARE the seeded descriptors
    seeded = bow.kmeans(key, desc, jnp.zeros(64), k=8, iters=1)
    np.testing.assert_array_equal(np.asarray(cents), np.asarray(seeded))
