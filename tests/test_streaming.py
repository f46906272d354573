"""Streaming row-carry execution (PR 4): the carry machinery of
kernels/stencil.py — step-0 ring priming, tail steps, stride/upsample phase
handoff across step boundaries, batched/multichannel carry isolation, and
bit-exactness of streaming vs overlapping-window vs `ref.chain_ref` for
every Stage kind — plus the measured-mode autotune contract
(`autotune.measure_chain` / `chain_stream_plan` / streaming working set).

Block heights at lmul=1: u8 rows=32, bf16 rows=16, f32 rows=8 — the f32
shapes below run 5-12 sequential grid steps, so rings are exercised hard
(priming at step 0, rotation at every later step, the P-not-dividing-N
plane-block tail, and H-not-dividing-rows row tails)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.autotune import (chain_iface, chain_stream_plan,
                                 chain_working_set, pick_chain_lmul,
                                 resolve_chain)
from repro.core.vector import VectorConfig
from repro.kernels import ref, stencil

DTYPES3 = [jnp.uint8, jnp.float32, jnp.bfloat16]


def _image(rng, shape, dtype):
    if dtype == jnp.uint8:
        return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32) * 100).astype(dtype)


def _as_tuple(x):
    return x if isinstance(x, tuple) else (x,)


def _assert_stream_equals_window(img, chain, lmul=1):
    """The tentpole invariant: the row-carry plan is bit-identical to the
    overlapping-window plan (same expressions over the same row windows —
    the ring only replaces recompute)."""
    vc = VectorConfig(lmul=lmul)
    w = _as_tuple(stencil.fused_chain(img, chain, vc=vc, mode="window"))
    s = _as_tuple(stencil.fused_chain(img, chain, vc=vc, mode="streaming"))
    assert len(w) == len(s)
    for a, b in zip(w, s):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return s


# ---------------------------------------------------------------------------
# bit-exactness per Stage kind (streaming vs window vs chain_ref),
# u8 / f32 / bf16 — H=70 at f32 rows=8 runs 9+ steps with a row tail
# ---------------------------------------------------------------------------

def _stencil_kinds(rng):
    k33 = jnp.asarray(rng.standard_normal((3, 3)) * 0.1, jnp.float32)
    return [
        ("filter2d", (stencil.filter_stage(k33),)),
        ("sep_filter", (stencil.gaussian_stage(5),)),
        ("box", (stencil.box_stage(2),)),
        ("erode", (stencil.erode_stage(2),)),
        ("dilate", (stencil.dilate_stage(1),)),
        ("threshold", (stencil.gaussian_stage(5),
                       stencil.threshold_stage(100.0))),
        ("affine", (stencil.gaussian_stage(3), stencil.affine_stage(0.5, 10.0))),
        ("grad_mag", (stencil.grad_stage(),)),
        ("sobel_emit", (stencil.sobel_stage(),)),
        ("sobel_reduce", (stencil.gaussian_stage(3), stencil.sobel_stage(),
                          stencil.grad_stage())),
        ("pyr_down_map", (stencil.gaussian_stage(5), stencil.pyr_down_stage(),
                          stencil.erode_stage(1))),
        ("pyr_down_tap", (stencil.gaussian_stage(5),
                          stencil.gaussian_stage(5, tap=-1),
                          stencil.pyr_down_stage(tap=1))),
        ("resize2", (stencil.resize2_stage(), stencil.gaussian_stage(3))),
        ("pyr_up", (stencil.pyr_up_stage(), stencil.gaussian_stage(3))),
        ("tap_ladder", (stencil.gaussian_stage(7, 1.6),
                        stencil.gaussian_stage(5, 1.2, tap=-1),
                        stencil.gaussian_stage(5, 1.5, tap=-1))),
    ]


@pytest.mark.parametrize("dtype", DTYPES3)
def test_stream_matches_window_every_kind(rng, dtype):
    img = _image(rng, (70, 90), dtype)
    for name, chain in _stencil_kinds(rng):
        outs = _assert_stream_equals_window(img, chain)
        # and both match the oracle (the repo-wide tolerance policy:
        # u8/bf16 float-accumulating stages may differ from the oracle's
        # slice-sum form by one rounding tie; streaming vs window above is
        # EXACT, which is the carry-machinery invariant under test)
        wants = _as_tuple(ref.chain_ref(img, chain))
        for o, w in zip(outs, wants):
            assert o.shape == w.shape and o.dtype == w.dtype
            if dtype == jnp.uint8:
                assert int(jnp.max(jnp.abs(o.astype(jnp.int32)
                                           - w.astype(jnp.int32)))) <= 1, name
            else:
                np.testing.assert_allclose(
                    np.asarray(o, np.float32), np.asarray(w, np.float32),
                    rtol=2e-2 if dtype == jnp.bfloat16 else 2e-5,
                    atol=1.0 if dtype == jnp.bfloat16 else 2e-3,
                    err_msg=name)


def test_stream_exact_vs_chain_ref_u8_morph(rng):
    """Morphology/threshold-only chains are bit-exact against the oracle in
    BOTH plans (no float accumulation, no tie hazard)."""
    img = _image(rng, (70, 90), jnp.uint8)
    chain = (stencil.erode_stage(2), stencil.dilate_stage(1),
             stencil.threshold_stage(127.5))
    s = _assert_stream_equals_window(img, chain)
    np.testing.assert_array_equal(np.asarray(s[0]),
                                  np.asarray(ref.chain_ref(img, chain)))


# ---------------------------------------------------------------------------
# gather stages: streaming must meet the same bit-exactness standard as
# window mode — vs the JITTED oracle (coordinate arithmetic is
# context-rounded by XLA, the repo's documented gather caveat)
# ---------------------------------------------------------------------------

def _jit_ref(img, chain):
    out = jax.jit(lambda x: ref.chain_ref(x, chain))(img)
    return _as_tuple(out)


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.float32])
def test_stream_gather_matches_jit_ref(rng, dtype):
    th = 0.05
    M = np.array([[np.cos(th), -np.sin(th), 3.0],
                  [np.sin(th), np.cos(th), -2.0]])
    img = _image(rng, (70, 61), dtype)
    chain = (stencil.warp_affine_stage(M, shape=(70, 61)),)
    s = _as_tuple(stencil.fused_chain(img, chain, vc=VectorConfig(lmul=1),
                                      mode="streaming"))
    for o, w in zip(s, _jit_ref(img, chain)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(w))
    yy, xx = np.mgrid[0:70, 0:61].astype(np.float32)
    chain2 = (stencil.remap_stage(xx + np.cos(yy / 3.0),
                                  yy + np.sin(xx / 4.0), extend=(1, 1)),
              stencil.erode_stage(1))
    s2 = _as_tuple(stencil.fused_chain(img, chain2, vc=VectorConfig(lmul=1),
                                       mode="streaming"))
    for o, w in zip(s2, _jit_ref(img, chain2)):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(w))


def test_stream_gather_integer_coords_matches_window(rng):
    """Integer-coordinate gathers have no rounding sensitivity: streaming
    == window == shifted input, exactly — including the ring-primed steps
    (the gather primes from the true input window at step 0)."""
    img = _image(rng, (70, 61), jnp.uint8)
    m = np.array([[1.0, 0.0, 3.0], [0.0, 1.0, -2.0]])
    chain = (stencil.warp_affine_stage(m, shape=(70, 61), extend=(2, 2)),
             stencil.erode_stage(2))
    _assert_stream_equals_window(img, chain)


def test_stream_warp_ladder_delay_fifos(rng):
    """The warp band rides the delay FIFOs through the whole ladder: band 0
    of the fused output must equal the standalone warp (streaming keeps
    the gather's values independent of what is fused behind it)."""
    th = 0.05
    M = np.array([[np.cos(th), -np.sin(th), 4.0],
                  [np.sin(th), np.cos(th), -3.0]])
    img = _image(rng, (64, 96), jnp.float32)
    ladder = (stencil.gaussian_stage(5, 1.6, tap=-1),
              stencil.gaussian_stage(5, 1.2, tap=-1))
    ey, ex = stencil.chain_halo(ladder)
    chain = (stencil.warp_affine_stage(M, shape=(64, 96),
                                       extend=(ey, ex)),) + ladder
    outs = _as_tuple(stencil.fused_chain(img, chain, vc=VectorConfig(lmul=1),
                                         mode="streaming"))
    alone = stencil.fused_chain(
        img, (stencil.warp_affine_stage(M, shape=(64, 96),
                                        extend=(ey, ex)),),
        vc=VectorConfig(lmul=1), mode="streaming")
    # coordinate arithmetic is context-rounded by XLA (different fused
    # programs can differ by a coordinate ulp x local gradient — the
    # repo-wide gather caveat), so exact equality is only guaranteed
    # within one program; a delay-FIFO misrouting would shift whole rows
    # (errors on the order of the image dynamic range), which this bound
    # rejects
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(alone),
                               rtol=1e-4, atol=2e-2)


# ---------------------------------------------------------------------------
# carry mechanics: priming, tails, phase handoff, plane isolation
# ---------------------------------------------------------------------------

def test_single_step_grid_degenerates_to_window(rng):
    """H < rows: only grid step 0 exists, so streaming IS the (priming)
    window pass — same launch count, same result."""
    img = _image(rng, (7, 90), jnp.float32)      # rows=8 at lmul1
    chain = (stencil.gaussian_stage(3), stencil.erode_stage(1))
    _assert_stream_equals_window(img, chain)


@pytest.mark.parametrize("H", [8, 9, 15, 16, 17, 33])
def test_row_tail_steps(rng, H):
    """H not dividing rows: the final grid step's window hangs past the
    image into replicate padding; ring contents there must still agree."""
    img = _image(rng, (H, 50), jnp.float32)
    chain = (stencil.gaussian_stage(5), stencil.box_stage(1))
    _assert_stream_equals_window(img, chain)


@pytest.mark.parametrize("H", [37, 48, 70, 75])
def test_stride_phase_handoff(rng, H):
    """2x decimation must stay image-phase-aligned when output rows cross
    grid-step boundaries (odd offsets, ceil-half geometry)."""
    img = _image(rng, (H, 61), jnp.uint8)
    _assert_stream_equals_window(
        img, (stencil.gaussian_stage(5), stencil.pyr_down_stage(),
              stencil.erode_stage(1)))
    _assert_stream_equals_window(
        img, (stencil.resize2_stage(), stencil.gaussian_stage(3)))


@pytest.mark.parametrize("H", [19, 31, 48])
def test_upsample_phase_handoff(rng, H):
    """pyr_up's even/odd output phases interleave across step boundaries:
    the ring carries 2*halo (+1 on odd-phase interfaces) source rows and
    the streamed window keeps the same parity every step."""
    img = _image(rng, (H, 31), jnp.float32)
    _assert_stream_equals_window(img, (stencil.pyr_up_stage(),))
    _assert_stream_equals_window(
        img, (stencil.pyr_up_stage(), stencil.gaussian_stage(5)))
    _assert_stream_equals_window(
        img, (stencil.pyr_down_stage(), stencil.pyr_up_stage()))


def test_batched_multichannel_carry_isolation(rng):
    """(B, H, W, C) -> N=B*C planes: the plane-block grid axis advances
    OUTSIDE the row axis, so step 0 of each plane block re-primes every
    ring — no cross-plane bleed, including the padded plane-block tail
    (N=6 planes at plane block 4 pads 2)."""
    chain = (stencil.gaussian_stage(5), stencil.gaussian_stage(5, tap=-1),
             stencil.erode_stage(1))
    img = _image(rng, (2, 70, 49, 3), jnp.uint8)
    outs = _assert_stream_equals_window(img, chain)
    # per-plane independence: each image/channel must equal its own
    # single-plane run (any ring bleed would couple adjacent planes)
    for b in range(2):
        for c in range(3):
            solo = _as_tuple(stencil.fused_chain(
                img[b, :, :, c], chain, vc=VectorConfig(lmul=1),
                mode="streaming"))
            for k, o in enumerate(outs):
                np.testing.assert_array_equal(
                    np.asarray(o[b, :, :, c]), np.asarray(solo[k]),
                    err_msg=f"plane ({b},{c}) band {k} bleed")


def test_lmul_invariance_streaming(rng):
    """Block height changes step boundaries and every ring size; results
    must not move (the paper's correctness property, carried over)."""
    img = _image(rng, (70, 90), jnp.uint8)
    chain = (stencil.gaussian_stage(5), stencil.gaussian_stage(5, tap=-1),
             stencil.pyr_down_stage(tap=0))
    outs = [stencil.fused_chain(img, chain, vc=VectorConfig(lmul=l),
                                mode="streaming") for l in (1, 2, 4, 8)]
    for o in outs[1:]:
        for a, b in zip(outs[0], o):
            assert (a == b).all()


# ---------------------------------------------------------------------------
# plan + autotune contract
# ---------------------------------------------------------------------------

def test_chain_stream_plan_ring_rows():
    """Plain/strided stages carry exactly 2*halo input rows; pyr_up carries
    2*halo (+1 when the streamed interface lands on an odd phase); the
    carry window always abuts the upstream stage's new rows."""
    chain = (stencil.gaussian_stage(5), stencil.erode_stage(1),
             stencil.pyr_down_stage(), stencil.pyr_up_stage())
    plan = resolve_chain(chain)
    for rows in (8, 16, 32):
        iface = chain_iface(plan, rows)
        sp = chain_stream_plan(plan, iface)
        for k, ((op, mode, halo, stride, up, *_), (sin_off, sin_r, ring, d)) \
                in enumerate(zip(plan, sp)):
            if up[0] > 1:
                assert ring in (2 * halo[0], 2 * halo[0] + 1), op
            else:
                assert ring == 2 * halo[0], op
            # continuity with the window interface (priming reads the tail)
            assert sin_off + sin_r == iface[k][1] + iface[k][2]


def test_streaming_working_set_smaller():
    """The ring-carry footprint undercuts the accumulated-halo window for
    deep ladders — that is why streaming can pick wider blocks."""
    ladder = (stencil.gaussian_stage(7, 1.6),
              stencil.gaussian_stage(7, 1.2, tap=-1),
              stencil.gaussian_stage(7, 1.5, tap=-1),
              stencil.gaussian_stage(7, 1.9, tap=-1))
    vc = VectorConfig(lmul=4)
    for w in (512, 1920):
        ws_win = chain_working_set(ladder, w, jnp.float32).bytes(vc)
        ws_str = chain_working_set(ladder, w, jnp.float32,
                                   streaming=True).bytes(vc)
        assert ws_str < ws_win
        assert (pick_chain_lmul(ladder, w, jnp.float32, streaming=True).lmul
                >= pick_chain_lmul(ladder, w, jnp.float32).lmul)
    # shallow pointwise chain: both models coincide on the input window
    flat = (stencil.threshold_stage(10.0),)
    assert (chain_working_set(flat, 512, streaming=True).bytes(vc)
            <= chain_working_set(flat, 512).bytes(vc))


def test_mode_ref_and_launch_counts(rng):
    img = _image(rng, (40, 56), jnp.uint8)
    chain = (stencil.gaussian_stage(5), stencil.threshold_stage(90.0))
    vc = VectorConfig(lmul=1)
    r = stencil.fused_chain(img, chain, vc=vc, mode="ref")
    assert stencil.count_pallas_calls(
        lambda x: stencil.fused_chain(x, chain, vc=vc, mode="ref"), img) == 0
    np.testing.assert_array_equal(np.asarray(r),
                                  np.asarray(ref.chain_ref(img, chain)))
    for m in ("streaming", "window"):
        n = stencil.count_pallas_calls(
            lambda x: stencil.fused_chain(x, chain, vc=vc, mode=m), img)
        assert n == 1, m
    with pytest.raises(ValueError, match="mode"):
        stencil.fused_chain(img, chain, vc=vc, mode="bogus")


def test_measure_chain_caches_and_routes(rng):
    """measure_chain times the candidate plans, caches the winner per
    (chain signature, shape, dtype, backend), and fused_chain's auto mode
    routes to it — identical values either way."""
    img = _image(rng, (40, 56), jnp.uint8)
    chain = (stencil.erode_stage(1),)
    vc = VectorConfig(lmul=1)
    autotune.clear_mode_cache()
    try:
        assert autotune.cached_chain_mode(chain, img.shape, img.dtype,
                                          vc) is None
        res = autotune.measure_chain(img, chain, vc=vc, n=1, persist=False)
        assert res["mode"] in autotune.CHAIN_MODES
        assert set(res["times"]) <= set(autotune.CHAIN_MODES)
        assert autotune.cached_chain_mode(chain, img.shape, img.dtype,
                                          vc) == res["mode"]
        # a different shape or block geometry is a different cache line
        assert autotune.cached_chain_mode(chain, (8, 8), img.dtype,
                                          vc) is None
        assert autotune.cached_chain_mode(chain, img.shape, img.dtype,
                                          VectorConfig(lmul=8)) is None
        auto = stencil.fused_chain(img, chain, vc=vc)       # routed
        want = ref.chain_ref(img, chain)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(want))
    finally:
        autotune.clear_mode_cache()


def test_auto_heuristic_pointwise_uses_window(rng):
    """A halo-free chain has nothing to carry: streaming mode allocates no
    rings and lowers to the plain window kernel (still one pallas_call)."""
    img = _image(rng, (40, 56), jnp.uint8)
    chain = (stencil.threshold_stage(90.0), stencil.affine_stage(2.0))
    out = _assert_stream_equals_window(img, chain)
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(ref.chain_ref(img, chain)))
