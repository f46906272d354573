#!/usr/bin/env python3
"""Smoke run of CvEngine's served path on a TPU, Pallas kernels doing the work.

    python3 chip_smoke.py                 # one chip: device, extract, classify
    python3 chip_smoke.py --four-chips    # four chips: the sharded path only

One process, no subprocesses, no fallback.  The engine installs no
degradation ladder (stencil rung "streaming" only, classifier tail
without a ladder), so a kernel that fails to lower fails the run instead
of giving way to the jnp floor; any degradation event fails it too.

Phases (any failure exits nonzero and prints no result line):

  device    jax.devices()[0] must be a TPU; never falls back to the CPU.
  extract   8 seeded u8 C3 1080p frames through CvEngine (fused
            blur -> erode -> grad preprocess, one SIFT octave, 32
            keypoints); every response served by "streaming", the lowered
            program holds tpu_custom_calls, and descriptors match an
            engine on the jnp floor (ladder ("ref",)) within the oracle
            tolerance.
  classify  k=250 dictionary + SVM head trained on 16 frames, 8 frames
            served through CvEngine.classify; the ClassifyPlan tail is
            two fused pallas_calls whose histograms and labels are
            bit-identical to its staged oracle on the same descriptors.
  four-chips  (--four-chips only) 32 frames through CvEngine over a
            4-device mesh: collective path taken, 4 distinct serving
            devices, output bit-identical to the same batch on one device.

The last line of standard output is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
FRAME_HW = (1080, 1920)
MAX_KP = 32
DICT_SIZE = 250               # the paper's dictionary size
DESC_ATOL = 2e-3              # f32 oracle tolerance (tests/test_streaming.py)
SHARDED_CHIPS = 4


class SmokeFailure(AssertionError):
    """A phase's contract did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _use_repo() -> None:
    """Put the checkout's `src/` on the path; a lone copy of this script
    has nothing to run."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run chip_smoke.py "
                           "from the root of a checkout")
    if src not in sys.path:
        sys.path.insert(0, src)


def frames(n: int, hw=FRAME_HW, *, split: str = "test", seed: int = 0):
    """n seeded u8 C3 frames (H, W, 3) and their class labels."""
    from repro.data.synthetic import ImageStream
    return ImageStream(res=tuple(hw), seed=seed).batch(n, split=split)


def _config(**kw):
    from repro.cv.config import PipelineConfig
    return PipelineConfig(max_kp=MAX_KP, preprocess=True, n_octaves=1,
                          classify_ladder=None, **kw)


def _engine(cfg, hw, *, model=None, ladder=("streaming",), mesh=None,
            max_batch: int = 64):
    from repro.serve.cv_engine import CvEngine
    return CvEngine(model, config=cfg, buckets=(tuple(hw),), ladder=ladder,
                    mesh=mesh, max_batch=max_batch)


def _no_degradation(phase: str) -> None:
    from repro.core import faultinject
    log = faultinject.degradation_log()
    check(not log, f"{phase}: {len(log)} degradation event(s): "
                   + "; ".join(f"{e.stage}:{e.from_plan}->{e.to_plan} "
                               f"({e.reason})" for e in log[:4]))


def _served(res, phase: str, plan: str = "streaming") -> None:
    bad = [r for r in res if not r.ok or r.plan != plan]
    check(not bad, f"{phase}: {len(bad)}/{len(res)} responses not served by "
                   f"{plan!r}: " + "; ".join(
                       f"#{r.index} ok={r.ok} plan={r.plan} err={r.error}"
                       for r in bad[:4]))


def _desc_match(got, want, phase: str) -> float:
    """Order-insensitive descriptor comparison per frame: equal validity
    counts, and every valid descriptor within DESC_ATOL of one on the
    other side (top-k ties may order keypoints differently)."""
    import numpy as np
    worst = 0.0
    for k, (g, w) in enumerate(zip(got, want)):
        gv, wv = g.desc[g.valid], w.desc[w.valid]
        check(len(gv) == len(wv), f"{phase}: frame {k} has {len(gv)} valid "
                                  f"keypoints, the oracle {len(wv)}")
        if len(gv):
            d = np.abs(gv[:, None, :] - wv[None, :, :]).max(axis=-1)
            worst = max(worst, float(d.min(axis=1).max()),
                        float(d.min(axis=0).max()))
    check(worst <= DESC_ATOL, f"{phase}: descriptor mismatch {worst:.3g} > "
                              f"{DESC_ATOL} against the oracle")
    return worst


def phase_device(platform: str = "tpu") -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    check(d.platform == platform,
          f"device: jax.devices()[0] is {d.platform!r}, not {platform!r}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_extract(imgs) -> dict:
    """Serve `imgs` (B, H, W, 3) u8 through the extract engine; returns
    the responses and what the phase counted.  (On the CPU the kernels
    are interpreted, so only the TPU's program can hold custom calls.)"""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import faultinject
    from repro.kernels import stencil

    hw = imgs.shape[1:3]
    cfg = _config()
    batch = np.asarray(imgs)
    faultinject.clear_degradation_log()
    eng = _engine(cfg, hw)
    t0 = time.perf_counter()
    res = eng.extract(list(batch))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.extract(list(batch))
    warm = time.perf_counter() - t0
    _served(res, "extract")
    _no_degradation("extract")

    fn = lambda x: eng._batch_body(x, "streaming")          # noqa: E731
    n_pallas = stencil.count_pallas_calls(fn, jnp.asarray(batch))
    check(n_pallas > 0, "extract: the served program has no pallas_call")
    n_tcc = jax.jit(fn).lower(jnp.asarray(batch)).as_text().count(
        "tpu_custom_call")
    if jax.devices()[0].platform == "tpu":
        check(n_tcc > 0, "extract: no tpu_custom_call in the lowered program")

    ref = _engine(cfg, hw, ladder=("ref",))
    rres = ref.extract(list(batch))
    _served(rres, "extract oracle", plan="ref")
    worst = _desc_match(res, rres, "extract")
    _no_degradation("extract")
    return {"responses": res, "cold_s": cold, "warm_s": warm,
            "pallas_calls": n_pallas, "tpu_custom_calls": n_tcc,
            "desc_max_diff": worst,
            "valid_kp": int(sum(int(r.valid.sum()) for r in res))}


def phase_classify(train_imgs, train_labels, imgs, extracted, *,
                   dict_size: int = DICT_SIZE, n_classes: int = 10) -> dict:
    """Train the BoW + SVM model, serve `imgs` through classify, and hold
    the fused tail to its staged oracle on the extract phase's
    descriptors (`extracted`: that phase's responses for `imgs`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import faultinject
    from repro.cv import pipeline
    from repro.kernels import stencil

    hw = imgs.shape[1:3]
    faultinject.clear_degradation_log()
    t0 = time.perf_counter()
    model = pipeline.train(jax.random.PRNGKey(0), train_imgs, train_labels,
                           _config(mode="streaming"), n_classes=n_classes,
                           dict_size=dict_size)
    jax.block_until_ready(model.centroids)
    train_s = time.perf_counter() - t0
    check(bool(jnp.all(jnp.isfinite(model.centroids))),
          "classify: non-finite dictionary")
    eng = _engine(_config(), hw, model=model)
    batch = list(np.asarray(imgs))
    t0 = time.perf_counter()
    res = eng.classify(batch)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.classify(batch)
    warm = time.perf_counter() - t0
    _served(res, "classify")

    plan = eng.plan
    desc = jnp.asarray(np.stack([r.desc for r in extracted]))
    valid = jnp.asarray(np.stack([r.valid for r in extracted]))
    tail = lambda d, v: plan.classify(plan.histograms(d, v, mode="fused"),  # noqa: E731
                                      mode="fused")
    n_pallas = stencil.count_pallas_calls(tail, desc, valid)
    check(n_pallas == 2, f"classify: fused tail has {n_pallas} pallas_calls, "
                         "expected 2")
    hf = plan.histograms(desc, valid, mode="fused")
    hr = plan.histograms(desc, valid, mode="ref")
    check(np.array_equal(np.asarray(hf), np.asarray(hr)),
          "classify: fused histograms differ from the staged oracle in "
          f"{int(np.sum(np.asarray(hf) != np.asarray(hr)))} words")
    pf = np.asarray(plan.classify(hf, mode="fused"))
    pr = np.asarray(plan.classify(hr, mode="ref"))
    check(np.array_equal(pf, pr), f"classify: fused labels {pf.tolist()} != "
                                  f"oracle labels {pr.tolist()}")
    served = np.asarray([r.pred for r in res])
    check(np.array_equal(served, pf), f"classify: served labels "
                                      f"{served.tolist()} != {pf.tolist()}")
    _no_degradation("classify")
    return {"train_s": train_s, "cold_s": cold, "warm_s": warm,
            "pallas_calls": n_pallas, "labels": pf.tolist()}


def phase_four_chips(imgs) -> dict:
    """The sharded serving path over SHARDED_CHIPS devices against one."""
    import numpy as np
    from repro.core import faultinject
    from repro.launch.mesh import make_cv_mesh

    hw = imgs.shape[1:3]
    cfg = _config()
    batch = list(np.asarray(imgs))
    mesh = make_cv_mesh()
    check(mesh.devices.size == SHARDED_CHIPS,
          f"four-chips: mesh has {mesh.devices.size} devices, not "
          f"{SHARDED_CHIPS}")
    faultinject.clear_degradation_log()
    eng = _engine(cfg, hw, mesh=mesh, max_batch=len(batch))
    t0 = time.perf_counter()
    res = eng.extract(batch)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng.extract(batch)
    warm = time.perf_counter() - t0
    _served(res, "four-chips")
    stats = eng.dispatcher.stats
    check(stats["collective_batches"] > 0,
          f"four-chips: collective path not taken ({stats})")
    devices = sorted({r.device for r in res})
    check(len(devices) == SHARDED_CHIPS,
          f"four-chips: served by {devices}, not {SHARDED_CHIPS} devices")
    one = _engine(cfg, hw, max_batch=len(batch)).extract(batch)
    _served(one, "four-chips single device")
    for k, (a, b) in enumerate(zip(res, one)):
        check(np.array_equal(a.desc, b.desc)
              and np.array_equal(a.valid, b.valid),
              f"four-chips: frame {k} differs from the one-device run")
    _no_degradation("four-chips")
    return {"cold_s": cold, "warm_s": warm, "devices": devices,
            "collective_batches": stats["collective_batches"]}


def run(four_chips: bool = False) -> dict:
    """All phases in order; returns the device record of the result line."""
    _use_repo()
    from repro.core import jaxcache

    t0 = time.perf_counter()
    dev = phase_device()
    cache_dir = jaxcache.enable()       # before the first compile
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}; "
          f"compile cache {cache_dir}", flush=True)
    times = {"device": time.perf_counter() - t0}
    if four_chips:
        t0 = time.perf_counter()
        imgs, _ = frames(32)
        out = phase_four_chips(imgs)
        times["four_chips"] = time.perf_counter() - t0
        print(f"four-chips: 32 frames on {out['devices']}, collective "
              f"batches {out['collective_batches']}, bit-identical to one "
              f"device; serve cold {out['cold_s']:.3f}s warm "
              f"{out['warm_s']:.3f}s", flush=True)
    else:
        t0 = time.perf_counter()
        imgs, _ = frames(8)
        ex = phase_extract(imgs)
        times["extract"] = time.perf_counter() - t0
        print(f"extract: {len(ex['responses'])} responses all 'streaming', "
              f"0 degradation events, {ex['pallas_calls']} pallas_calls / "
              f"{ex['tpu_custom_calls']} tpu_custom_calls, "
              f"{ex['valid_kp']} valid keypoints, oracle max |d desc| "
              f"{ex['desc_max_diff']:.3g}; serve cold {ex['cold_s']:.3f}s "
              f"warm {ex['warm_s']:.3f}s", flush=True)
        t0 = time.perf_counter()
        train, labels = frames(16, split="train")
        cl = phase_classify(train, labels, imgs, ex["responses"])
        times["classify"] = time.perf_counter() - t0
        print(f"classify: k={DICT_SIZE} trained in {cl['train_s']:.3f}s, "
              f"{cl['pallas_calls']} fused pallas_calls, histograms and "
              f"labels {cl['labels']} bit-identical to the oracle; serve "
              f"cold {cl['cold_s']:.3f}s warm {cl['warm_s']:.3f}s",
              flush=True)
    c = jaxcache.counts()
    print("summary: " + ", ".join(f"{k} {v:.3f}s" for k, v in times.items())
          + f"; compiles {c['compiled']}, cache hits {c['hits']} of "
            f"{c['requests']} requests", flush=True)
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path over 4 devices and its "
                         "one-device comparison")
    a = ap.parse_args(argv)
    try:
        dev = run(four_chips=a.four_chips)
    except Exception as e:                      # noqa: BLE001 — report, exit 1
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
