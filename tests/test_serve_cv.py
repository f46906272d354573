"""Fault-tolerant serving engine + degradation-ladder contract (PR-6).

Every fault class in core.faultinject must be *survived* by CvEngine —
outputs bit-identical to the chain_ref floor where the ladder lands
there, a structured degradation event recorded, zero unhandled
exceptions — and the pre-existing structural chain_ref fallbacks
(planes <= accumulated halo; pyramid staged tails) must stay
bit-identical to `ref.chain_ref` under serving bucket shapes."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess
from repro.core import faultinject
from repro.cv import PipelineConfig, features, pipeline
from repro.kernels import ref, stencil
from repro.serve.cv_engine import CvEngine, Request


@pytest.fixture(autouse=True)
def _clean_faults():
    """Tests are fault-free unless they install their own spec (the chaos
    CI cell's process-wide REPRO_FAULT_SPEC must not skew these asserts);
    the explicit chaos-gate test re-reads the env itself."""
    with faultinject.inject(None):
        faultinject.clear_degradation_log()
        yield
    faultinject.clear_degradation_log()


def _gray_f32(n, lo=40, hi=48, seed=0):
    gen = np.random.default_rng(seed)
    return [gen.random((int(gen.integers(lo, hi + 1)),
                        int(gen.integers(lo, hi + 1))),
                       dtype=np.float32) for _ in range(n)]


def _rgb_u8(n, lo=24, hi=32, seed=1):
    gen = np.random.default_rng(seed)
    return [gen.integers(0, 256, (int(gen.integers(lo, hi + 1)),
                                  int(gen.integers(lo, hi + 1)), 3),
                         dtype=np.uint8) for _ in range(n)]


def _filter_chain(seed=0):
    """filter2D 5x5 (OpenCV perf's draw: uniform in [-3, 10) over the
    absolute value of its sum) -> 3x3 erode, the served filter task's chain."""
    k = np.random.default_rng(seed).uniform(-3, 10, (5, 5))
    return (stencil.filter_stage((k / abs(k.sum())).astype(np.float32)),
            stencil.erode_stage(1))


def _expected(eng, mode):
    """Recompute descriptors for every captured canonical batch at an
    explicit rung — the engine's output contract is defined on the padded
    + sanitized frames it actually processed."""
    outs = []
    for _, batch in eng.captured:
        feats = pipeline.extract_features(
            jnp.asarray(batch), PipelineConfig(max_kp=eng.max_kp, mode=mode),
            validate=False)
        outs.append((np.asarray(feats["desc"]), np.asarray(feats["valid"])))
    return outs


# ---------------------------------------------------------------------------
# engine correctness (fault-free)
# ---------------------------------------------------------------------------

def test_engine_matches_direct_pipeline():
    work = _rgb_u8(6)
    eng = CvEngine(buckets=((32, 32),), max_batch=8, max_kp=8,
                   capture_frames=True)
    res = eng.extract(work)
    assert all(r.ok for r in res)
    assert all(r.bucket == (32, 32) for r in res)
    assert all(r.plan == "streaming" for r in res)     # first rung held
    (desc, valid), = _expected(eng, "streaming")
    for k, r in enumerate(res):
        np.testing.assert_array_equal(r.desc, desc[k])
        np.testing.assert_array_equal(r.valid, valid[k])


def test_engine_splits_batches_and_buckets():
    work = _rgb_u8(5) + _gray_f32(3, lo=40, hi=44, seed=2)
    eng = CvEngine(buckets=((32, 32), (48, 48)), max_batch=4, max_kp=8)
    res = eng.extract(work)
    assert all(r.ok for r in res)
    assert [r.bucket for r in res[:5]] == [(32, 32)] * 5
    assert [r.bucket for r in res[5:]] == [(48, 48)] * 3
    assert eng.stats["served"] == 8


@pytest.mark.parametrize("task", ["extract", "filter"])
def test_submit_spans_each_phase_once_per_group(tmp_path, task):
    """Under a profiler capture one submit leaves a `serve.submit` span
    holding `serve.admit` once and every per-batch phase once per group
    batch (two buckets here), all nested inside it; the submit span names
    the engine's task."""
    import collections
    from jax.profiler import ProfileData
    work = _rgb_u8(3) + _gray_f32(2, lo=40, hi=44, seed=2)
    chain = _filter_chain() if task == "filter" else None
    eng = CvEngine(buckets=((32, 32), (48, 48)), max_batch=4, max_kp=8,
                   ladder=("ref",), chain=chain)
    eng.submit(work)                    # compiled outside the capture
    with jax.profiler.trace(str(tmp_path)):
        res = eng.submit(work)
    assert all(r.ok for r in res)
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = [e for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("serve.")]
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in events]
    per_batch = ("serve.stack", "serve.h2d", "serve.dispatch", "serve.wait",
                 "serve.d2h", "serve.assemble")
    assert collections.Counter(n for n, _, _ in spans) == {
        "serve.submit": 1, "serve.admit": 1, **{n: 2 for n in per_batch}}
    (_, lo, hi), = [s for s in spans if s[0] == "serve.submit"]
    assert all(lo <= s and e <= hi for _, s, e in spans)
    (submit,) = [e for e in events if e.name == "serve.submit"]
    assert dict(submit.stats)["task"] == task


def test_repeat_submit_reuses_compiled_program(tmp_path):
    """A second submit of the same bucket and batch size runs the program
    the first one compiled: no new trace, no compile request to the
    persistent cache."""
    out = run_subprocess(f"""
        os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
        import numpy as np
        from repro.core import jaxcache
        from repro.serve.cv_engine import CvEngine
        jaxcache.enable()
        gen = np.random.default_rng(4)
        work = [gen.integers(0, 256, (30, 28, 3), dtype=np.uint8)
                for _ in range(3)]
        eng = CvEngine(buckets=((32, 32),), max_kp=8)
        assert all(r.ok for r in eng.extract(work))
        t0, c0 = eng.stats["batch_traces"], jaxcache.counts()["requests"]
        assert all(r.ok for r in eng.extract(work[::-1]))
        print("REUSE", t0, eng.stats["batch_traces"] - t0,
              jaxcache.counts()["requests"] - c0)
    """, devices=1, timeout=300)
    assert "REUSE 1 0 0" in out


def test_new_batch_size_adds_one_trace():
    work = _rgb_u8(5, seed=6)
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    assert all(r.ok for r in eng.extract(work[:2]))
    assert eng.stats["batch_traces"] == 1
    assert all(r.ok for r in eng.extract(work[2:]))      # batch of 3
    assert eng.stats["batch_traces"] == 2
    assert all(r.ok for r in eng.extract(work[:3]))      # 3 again: cached
    assert eng.stats["batch_traces"] == 2


def test_clear_caches_retraces_held_programs():
    """`jax.clear_caches()`, which the fault tests use to make a shape
    trace again, also reaches the programs the engine holds."""
    work = _rgb_u8(2, seed=8)
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    eng.extract(work)
    eng.extract(work)
    assert eng.stats["batch_traces"] == 1
    jax.clear_caches()
    assert all(r.ok for r in eng.extract(work))
    assert eng.stats["batch_traces"] == 2


def test_classify_program_takes_the_model_as_an_argument():
    """Models that differ only in their weights lower to one program: the
    tail's arrays are arguments, not constants, so a new model reuses
    what the persistent compile cache holds."""
    work = _rgb_u8(2, lo=32, hi=32, seed=10)
    engines = []
    for seed in (0, 1):
        kc, kw = jax.random.split(jax.random.PRNGKey(seed))
        model = pipeline.BowSvmModel(
            centroids=jax.random.normal(kc, (16, 128)),
            svm={"w": jax.random.normal(kw, (3, 16)), "b": jnp.zeros(3)},
            n_classes=3)
        eng = CvEngine(model, buckets=((32, 32),), max_kp=8)
        assert all(r.ok and 0 <= r.pred < 3 for r in eng.classify(work))
        engines.append(eng)
    x = jnp.asarray(np.stack(work))
    texts = [e._local_fns["streaming"].lower(x, e.plan).as_text()
             for e in engines]
    assert texts[0] == texts[1]


def test_failed_trace_is_not_cached():
    """A lowering fault fires while the first rung traces; the batch
    degrades, and the next submit of the same shape traces that rung again
    and is served on it."""
    work = _gray_f32(3, seed=5)
    eng = CvEngine(buckets=((48, 48),), max_batch=8, max_kp=8,
                   max_retries=0)
    jax.clear_caches()
    with faultinject.inject("lowering_error:count=1"):
        first = eng.extract(work)
        second = eng.extract(work)
    assert all(r.ok and r.degraded and r.plan == "tiled2d" for r in first)
    assert all(r.ok and not r.degraded and r.plan == "streaming"
               for r in second)
    # failed streaming trace + tiled2d, then streaming traced again
    assert eng.stats["batch_traces"] == 3


@pytest.mark.parametrize("channels", [1, 3])
def test_filter_task_serves_chain_ref(channels):
    """Each u8 frame comes back as `ref.chain_ref` of the chain on that
    frame alone, u8 and in the frame's shape: a frame padded up to its
    bucket is cut back, and a float-accumulating stage may put a pixel at
    a .5 tie one apart."""
    gen = np.random.default_rng(11)
    shapes = [(48, 128), (48, 128), (41, 100)]
    work = [gen.integers(0, 256, hw + (channels,), dtype=np.uint8)
            for hw in shapes]
    chain = _filter_chain()
    eng = CvEngine(None, chain=chain, buckets=((48, 128),), max_batch=4,
                   ladder=("streaming",))
    res = eng.filter(work)
    for r, frame in zip(res, work):
        assert r.ok and r.plan == "streaming" and not r.degraded
        assert r.image.dtype == np.uint8 and r.image.shape == frame.shape
        want = np.asarray(ref.chain_ref(jnp.asarray(frame), chain))
        diff = np.abs(r.image.astype(np.int16) - want.astype(np.int16))
        assert diff.max() <= 1
        assert (diff > 0).mean() <= 1e-3
    assert eng.signature == ("cv:filter:(('filter2d', (), None), "
                             "('erode', (1,), None))")


def test_filter_program_takes_the_weights_as_an_argument():
    """Engines whose kernels differ only in their values trace once each
    and lower to one program: the weights are an argument, not a
    constant compiled in."""
    work = _rgb_u8(2, lo=32, hi=32, seed=12)
    engines = []
    for seed in (0, 1):
        eng = CvEngine(None, chain=_filter_chain(seed), buckets=((32, 32),),
                       ladder=("streaming",))
        for _ in range(2):
            assert all(r.ok for r in eng.filter(work))
        assert eng.stats["batch_traces"] == 1
        engines.append(eng)
    x = jnp.asarray(np.stack(work))
    texts = [e._local_fns["streaming"].lower(x, e._program_arg).as_text()
             for e in engines]
    assert texts[0] == texts[1]


def test_model_and_chain_together_are_refused():
    kc, kw = jax.random.split(jax.random.PRNGKey(0))
    model = pipeline.BowSvmModel(
        centroids=jax.random.normal(kc, (16, 128)),
        svm={"w": jax.random.normal(kw, (3, 16)), "b": jnp.zeros(3)},
        n_classes=3)
    with pytest.raises(ValueError):
        CvEngine(model, chain=_filter_chain())
    with pytest.raises(ValueError):     # a tap ladder ends in two bands
        CvEngine(None, chain=(stencil.gaussian_stage(3),
                              stencil.gaussian_stage(3, tap=-1)))
    with pytest.raises(ValueError):
        CvEngine(buckets=((32, 32),)).filter(_rgb_u8(1))


def test_filter_sharded_matches_local():
    """The sharded fan-out over four devices assembles the same images
    as the local program."""
    out = run_subprocess("""
        import numpy as np
        from repro.kernels import stencil
        from repro.launch.mesh import make_cv_mesh
        from repro.serve.cv_engine import CvEngine

        gen = np.random.default_rng(13)
        k = gen.uniform(-3, 10, (5, 5))
        chain = (stencil.filter_stage((k / abs(k.sum())).astype(np.float32)),
                 stencil.erode_stage(1))
        work = [gen.integers(0, 256, (32, 64, 1), dtype=np.uint8)
                for _ in range(8)]
        kw = dict(chain=chain, buckets=((32, 64),), max_batch=8,
                  ladder=("streaming",))
        sharded = CvEngine(None, mesh=make_cv_mesh(), **kw).filter(work)
        local = CvEngine(None, **kw).filter(work)
        assert all(r.ok and r.shard is not None for r in sharded)
        assert len({r.shard for r in sharded}) == 4
        for s, l in zip(sharded, local):
            np.testing.assert_array_equal(s.image, l.image)
        print("SHARDED same")
    """, devices=4)
    assert "SHARDED same" in out


def test_engine_rejects_malformed_frames():
    work = [np.zeros((8, 8, 2), np.uint8), np.zeros((8,), np.float32),
            np.zeros((16, 16), np.int32)] + _rgb_u8(1)
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    res = eng.extract(work)
    assert [r.ok for r in res] == [False, False, False, True]
    assert "bad_rank" in res[0].error and "bad_rank" in res[1].error
    assert "bad_dtype" in res[2].error


# ---------------------------------------------------------------------------
# fault classes: survived, chain_ref-identical, event recorded
# ---------------------------------------------------------------------------

def test_lowering_fault_degrades_to_chain_ref_identical():
    """lowering_error at p=1: every pallas rung (streaming, tiled2d,
    window) fails, the engine lands on the chain_ref floor; outputs are
    bit-identical to an explicit mode="ref" run over the same canonical
    frames."""
    work = _gray_f32(4)
    eng = CvEngine(buckets=((48, 48),), max_batch=8, max_kp=8,
                   max_retries=0, capture_frames=True)
    # injected lowering faults fire at TRACE time (like real lowering
    # errors); drop cached traces so this shape actually re-traces
    jax.clear_caches()
    with faultinject.inject("lowering_error"):
        res = eng.extract(work)
    assert all(r.ok for r in res)
    assert all(r.plan == "ref" for r in res)
    assert all(r.degraded for r in res)
    hops = [(e.from_plan, e.to_plan) for e in res[0].events]
    # the full 4-rung walk: every pallas plan fails, ref catches
    assert ("streaming", "tiled2d") in hops
    assert ("tiled2d", "window") in hops
    assert ("window", "ref") in hops
    assert all(e.injected for e in res[0].events)
    (desc, valid), = _expected(eng, "ref")
    for k, r in enumerate(res):
        np.testing.assert_array_equal(r.desc, desc[k])
        np.testing.assert_array_equal(r.valid, valid[k])


@pytest.mark.parametrize("task", ["extract", "filter"])
def test_floor_rung_failure_answers_every_frame(task):
    """When the last rung fails every attempt, submit still returns one
    Response per frame: not ok, with the floor's error."""
    work = _rgb_u8(3, lo=40, hi=48, seed=14)
    chain = _filter_chain() if task == "filter" else None
    eng = CvEngine(None, chain=chain, buckets=((48, 48),), max_batch=8,
                   max_kp=8,
                   max_retries=0, ladder=("streaming",))
    jax.clear_caches()
    with faultinject.inject("lowering_error"):
        res = eng.submit(work)
    assert len(res) == len(work)
    for k, r in enumerate(res):
        assert r.index == k and not r.ok and r.bucket == (48, 48)
        assert r.error.startswith("floor_rung_failed: InjectedFault")
        assert isinstance(r.events, list)
    assert eng.stats["errors"] == len(work) and eng.stats["served"] == 0


def test_transient_fault_retries_same_rung():
    """A count-bounded fault is transient: the bounded retry recovers the
    FIRST rung (no degradation past it) and records the retry event."""
    work = _gray_f32(4)
    eng = CvEngine(buckets=((48, 48),), max_batch=8, max_kp=8,
                   max_retries=1, backoff_s=0.0, capture_frames=True)
    jax.clear_caches()
    with faultinject.inject("lowering_error:count=1"):
        res = eng.extract(work)
    assert all(r.ok for r in res)
    assert all(r.plan == "streaming" for r in res)
    assert res[0].retries == 1
    assert any("retry" in e.reason for e in res[0].events)
    (desc, _), = _expected(eng, "streaming")
    for k, r in enumerate(res):
        np.testing.assert_array_equal(r.desc, desc[k])


def test_nan_poisoning_sanitized_with_event():
    work = _gray_f32(2, lo=28, hi=31, seed=3)
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    with faultinject.inject("nan_input"):
        res = eng.extract(work)
    assert all(r.ok for r in res)
    assert eng.stats["sanitized"] == 2
    ev = [e for r in res for e in r.events if e.to_plan == "sanitized"]
    assert ev and all(e.injected for e in ev)
    assert all(np.isfinite(r.desc).all() for r in res)


def test_nan_poisoning_reject_mode():
    work = _gray_f32(2, lo=28, hi=31, seed=3)
    eng = CvEngine(buckets=((32, 32),), max_kp=8, bad_input="reject")
    with faultinject.inject("nan_input"):
        res = eng.extract(work)
    assert all(not r.ok for r in res)
    assert all("bad_values" in r.error for r in res)


def test_bucket_miss_serves_exact_shape():
    work = _rgb_u8(2, lo=28, hi=28, seed=4)        # all (28, 28, 3)
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    with faultinject.inject("bucket_miss"):
        res = eng.extract(work)
    assert all(r.ok for r in res)
    assert all(r.bucket == (28, 28) for r in res)  # exact shape, no padding
    ev = [e for e in faultinject.degradation_log()
          if e.to_plan == "exact-shape"]
    assert ev and ev[0].injected


def test_oversized_frame_serves_exact_shape():
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    res = eng.extract(_gray_f32(1, lo=40, hi=40, seed=5))
    assert res[0].ok and res[0].bucket == (40, 40)
    assert any(e.to_plan == "exact-shape" and not e.injected
               for e in faultinject.degradation_log())


def test_warm_measure_timeout_degrades_to_heuristic():
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    with faultinject.inject("measure_timeout:count=1"):
        assert eng.warm((48, 48)) is None          # survived, not raised
    ev = [e for e in faultinject.degradation_log()
          if e.to_plan == "heuristic"]
    assert ev and "timed out" in ev[0].reason
    # fault exhausted: warming a structural-fallback bucket now succeeds
    entry = eng.warm((32, 32), deadline_s=60.0)
    assert entry is not None and entry["mode"] in stencil.MODES


def test_deadlines_pre_and_post():
    frame = _rgb_u8(1, lo=30, hi=30, seed=6)[0]
    eng = CvEngine(buckets=((32, 32),), max_kp=8)
    res = eng.submit([Request(frame, deadline=time.monotonic() - 1.0),
                      Request(frame, deadline=time.monotonic() + 0.002),
                      Request(frame)])
    assert not res[0].ok and res[0].error == "deadline_exceeded"
    assert res[2].ok and not res[2].deadline_missed
    # the 2ms deadline admits but cannot beat the batch compute: answered,
    # flagged late (post-compute miss is reported, not dropped)
    assert res[1].ok and res[1].deadline_missed
    assert eng.stats["deadline_missed"] == 2


def test_retry_backoff_never_sleeps_past_deadline():
    """Satellite: a retry whose backoff sleep would overrun the tightest
    request deadline is abandoned (counted deadline_missed, NOT a retry)
    and the ladder degrades immediately — the old behavior slept
    `backoff * 2**attempt` regardless and answered the whole batch late."""
    work = _gray_f32(2, seed=9)                  # 48x48: pallas rungs live
    # backoff so large that ANY retry sleep overruns a near deadline; the
    # generous retry budget must go entirely unused
    eng = CvEngine(buckets=((48, 48),), max_kp=8, max_retries=3,
                   backoff_s=120.0)
    jax.clear_caches()
    t0 = time.monotonic()
    with faultinject.inject("lowering_error:count=1"):
        res = eng.submit([Request(w, deadline=time.monotonic() + 1.0)
                          for w in work])
    assert time.monotonic() - t0 < 60.0      # never slept the 120s backoff
    assert all(r.ok for r in res)            # served by the next rung
    assert all(r.plan == "tiled2d" for r in res)
    assert eng.stats["retries"] == 0         # abandoned, not retried
    assert res[0].retries == 0
    assert any("retry abandoned" in e.reason for e in res[0].events)
    # same fault with no deadlines: the retry budget IS used (control)
    jax.clear_caches()
    eng2 = CvEngine(buckets=((32, 32),), max_kp=8, max_retries=3,
                    backoff_s=0.0)
    with faultinject.inject("lowering_error:count=1"):
        res2 = eng2.submit(work)
    assert all(r.ok and r.plan == "streaming" for r in res2)
    assert eng2.stats["retries"] == 1


# ---------------------------------------------------------------------------
# structural chain_ref fallbacks under serving bucket shapes (satellite)
# ---------------------------------------------------------------------------

def test_planes_le_halo_bit_identical_to_chain_ref():
    """32x32 (the CIFAR serving bucket) vs the octave chain's 34-row
    accumulated halo: every mode structurally falls back to ref.chain_ref
    — bit-identical, zero launches, event recorded."""
    gen = np.random.default_rng(7)
    img = jnp.asarray(gen.random((32, 32), dtype=np.float32))
    chain = features.octave_chain(with_next_base=False)
    want = [np.asarray(o) for o in ref.chain_ref(img, chain)]
    for mode in ("streaming", "window", "ref"):
        faultinject.clear_degradation_log()
        outs = stencil.fused_chain(img, chain, mode=mode)
        assert stencil.count_pallas_calls(
            lambda x: stencil.fused_chain(x, chain, mode=mode), img) == 0
        for got, exp in zip(outs, want):
            np.testing.assert_array_equal(np.asarray(got), exp)
        ev = faultinject.degradation_log()
        assert any(e.stage == "fused_chain" and e.to_plan == "ref"
                   and "planes<=halo" in e.reason for e in ev)


def test_planes_le_halo_fallback_survives_injected_fault():
    """The structural fallback never reaches the pallas path, so a p=1
    lowering fault cannot touch it — same bits, no ladder involvement."""
    gen = np.random.default_rng(8)
    img = jnp.asarray(gen.random((32, 32), dtype=np.float32))
    chain = features.octave_chain(with_next_base=False)
    want = [np.asarray(o) for o in ref.chain_ref(img, chain)]
    with faultinject.inject("lowering_error"):
        outs = stencil.fused_chain(img, chain, mode="streaming",
                                   ladder=("streaming", "window", "ref"))
    for got, exp in zip(outs, want):
        np.testing.assert_array_equal(np.asarray(got), exp)


def test_pyramid_staged_tail_bit_identical_to_chain_ref():
    """64x64 3-octave pyramid: link 2's 16x16 planes undershoot its 29-row
    halo — the tail runs ref.chain_ref on the carried base, bit-identical,
    with the structural event recorded."""
    gen = np.random.default_rng(9)
    g = jnp.asarray(gen.random((64, 64), dtype=np.float32))
    chains = features.pyramid_chains(3)
    # the tail's expected bits: walk the carry chain at the same rung
    outs0 = stencil.fused_chain(g, chains[0], mode="streaming")
    outs1 = stencil.fused_chain(outs0[-1], chains[1], mode="streaming")
    want_tail = [np.asarray(o) for o in ref.chain_ref(outs1[-1], chains[2])]
    faultinject.clear_degradation_log()
    outs_all, _ = stencil.chained_launches(g, chains, mode="streaming")
    for got, exp in zip(outs_all[2], want_tail):
        np.testing.assert_array_equal(np.asarray(got), exp)
    assert any(e.stage == "fused_chain" and "planes<=halo" in e.reason
               for e in faultinject.degradation_log())


def test_pyramid_under_faults_equals_ref_pyramid():
    """p=1 lowering faults walk every launchable link down the ladder to
    the chain_ref floor: the whole pyramid equals an explicit mode="ref"
    run bit-for-bit, with injected degradation events on each link."""
    gen = np.random.default_rng(10)
    g = jnp.asarray(gen.random((64, 64), dtype=np.float32))
    chains = features.pyramid_chains(3)
    want, _ = stencil.chained_launches(g, chains, mode="ref")
    faultinject.clear_degradation_log()
    with faultinject.inject("lowering_error"):
        got, _ = stencil.chained_launches(
            g, chains, mode="streaming", ladder=("streaming", "window", "ref"))
    for w_link, g_link in zip(want, got):
        for w, o in zip(w_link, g_link):
            np.testing.assert_array_equal(np.asarray(w), np.asarray(o))
    ev = [e for e in faultinject.degradation_log() if e.injected]
    assert {(e.from_plan, e.to_plan) for e in ev} >= \
        {("streaming", "window"), ("window", "ref")}


# ---------------------------------------------------------------------------
# pipeline input validation (satellite)
# ---------------------------------------------------------------------------

def test_extract_features_rejects_bad_rank_dtype():
    with pytest.raises(ValueError, match="rank"):
        pipeline.extract_features(np.zeros((16, 16), np.uint8))
    with pytest.raises(ValueError, match="dtype"):
        pipeline.extract_features(np.zeros((2, 16, 16), np.int32))
    with pytest.raises(ValueError, match="expected an array"):
        pipeline.extract_features([[1, 2], [3, 4]])


def test_extract_features_rejects_nan_inf():
    bad = np.zeros((2, 16, 16), np.float32)
    bad[0, 3, 3] = np.nan
    with pytest.raises(ValueError, match="NaN/Inf"):
        pipeline.extract_features(bad)
    bad[0, 3, 3] = np.inf
    with pytest.raises(ValueError, match="NaN/Inf"):
        pipeline.predict(None, bad)     # validation fires before model use


# ---------------------------------------------------------------------------
# chaos gate: the CI cell's end-to-end zero-unhandled-exceptions check
# ---------------------------------------------------------------------------

DEFAULT_CHAOS_SPEC = ("lowering_error:p=0.7,seed=5;nan_input:p=0.5;"
                      "bucket_miss:p=0.3;cache_corrupt;measure_timeout:p=0.5")


def test_chaos_workload_zero_unhandled_exceptions():
    spec = os.environ.get(faultinject.ENV_VAR) or DEFAULT_CHAOS_SPEC
    work = _rgb_u8(6, seed=11) + _gray_f32(2, lo=28, hi=31, seed=12)
    work.append(np.zeros((4, 4, 7), np.uint8))     # malformed rides along
    eng = CvEngine(buckets=((32, 32),), max_batch=4, max_kp=8)
    with faultinject.inject(spec):
        res = eng.extract(work)
    assert all(r is not None for r in res)
    assert all(r.ok for r in res[:-1])             # every well-formed frame
    assert not res[-1].ok
