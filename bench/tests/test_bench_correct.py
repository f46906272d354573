"""`correct` on the CPU at a tiny size: a sound run passes; the control
(the reference itself in bfloat16 in the program's place) and each
fault planted in the timed path come out not correct."""
import os
import subprocess
import sys

import pytest

import tinycells
from benchlib import harness

SEED = 2 ** 31 + 77


def _run(cell):
    return harness.run(cell, seed=SEED, seconds=1.0, trace=False,
                       platform="cpu", log=lambda m: None)


def _plant(monkeypatch, fault):
    """Break CvEngine's timed path underneath the harness."""
    import jax.numpy as jnp
    from repro.serve import cv_engine
    orig = cv_engine.CvEngine._batch_fn
    last = {}

    def ref_feats(self, x):
        from repro.cv import pipeline
        return pipeline.extract_features(x, self.config.replace(mode="ref"),
                                         validate=False)

    def broken(self, x, rung):
        if fault == "tail_half":        # half of each frame's keypoints
            from repro.cv import pipeline   # left out of its histogram
            f = pipeline.extract_features(x, self.config.replace(mode=rung),
                                          validate=False)
            v = f["valid"]
            keep = jnp.cumsum(v, axis=1) <= jnp.sum(v, axis=1,
                                                    keepdims=True) // 2
            h = self.plan.histograms(f["desc"], v & keep, mode="fused")
            return {"pred": self.plan.classify(h, mode="fused")}
        out = orig(self, x, rung)
        key = "desc" if "desc" in out else "pred"
        v = out[key]
        if fault == "altered":          # one frame's answer changed
            if key == "desc":
                v = v.at[0].set(jnp.roll(v[0], 1, axis=-1))
            else:                       # to the class it scores lowest
                p = self.plan
                feats = ref_feats(self, x)
                s = p.scores(p.histograms(feats["desc"], feats["valid"]))
                v = v.at[0].set(jnp.argmin(s[0]).astype(v.dtype))
        elif fault == "half":           # second half of the batch left out
            half = v.shape[0] // 2
            v = v.at[half:].set(0)
            if "valid" in out:
                out["valid"] = out["valid"].at[half:].set(False)
        elif fault == "stale":          # the previous submit's answer
            prev, last["v"] = last.get("v"), v
            v = prev if prev is not None else v
        out[key] = v
        return out

    monkeypatch.setattr(cv_engine.CvEngine, "_batch_fn", broken)


@pytest.mark.parametrize("name", ["sift1080p.b16", "bow250svm1080p.b16"])
def test_sound_run_is_correct(name):
    r = _run(tinycells.cell(name))
    assert r.correct, r.checks
    assert r.failed == 0 and r.attempted >= 2


@pytest.mark.parametrize("name", ["sift1080p.b16", "bow250svm1080p.b16"])
def test_control_is_not_correct(name):
    import readings
    cell = tinycells.cell(name)
    st = harness.Setup(cell, SEED, platform="cpu")
    served = list(range(cell.traffic["pool"]))
    refs = harness.references(cell, st.pool, st.head, served)
    low = harness.references(cell, st.pool, st.head, served,
                             dtype="bfloat16")
    checks = harness.judge(cell, readings.control_answers(cell.task, low), refs)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


# every cell: one answer altered, half of the batch left out, a stale
# answer; the classify cell also: half of each frame's keypoints left out
# of its histogram, a fault of the tail alone
FAULTS = [(name, fault) for fault in ("altered", "half", "stale")
          for name in ("sift1080p.b16", "bow250svm1080p.b16")]
FAULTS.append(("bow250svm1080p.b16", "tail_half"))


@pytest.mark.parametrize("name,fault", FAULTS)
def test_planted_fault_is_not_correct(monkeypatch, name, fault):
    _plant(monkeypatch, fault)
    r = _run(tinycells.cell(name, batch=4, pool=8))
    assert not r.correct, (fault, r.checks)


SHARDED = r"""
import sys
sys.path.insert(0, {tests!r})
import tinycells
from benchlib import harness
from repro.serve import shard_dispatch
fault = sys.argv[1]
if fault == "exchange":
    # every shard answers with what the first device computed: the
    # results of the other devices never come back
    orig = shard_dispatch.ShardDispatcher._collective_fn
    def local_only(self, fn, rung, shape, dtype):
        f = orig(self, fn, rung, shape, dtype)
        per = shape[0] // self.n_shards
        def g(x):
            out = f(x)
            return {{k: v[:per].repeat(self.n_shards, axis=0)
                     for k, v in out.items()}}
        return g
    shard_dispatch.ShardDispatcher._collective_fn = local_only
cell = tinycells.cell("sift1080p.b64x4", batch=8, pool=8)
r = harness.run(cell, seed={seed}, seconds=0, trace=False, platform="cpu",
                log=lambda m: None)
stats = r.checks
print("CORRECT", r.correct, stats)
"""


@pytest.mark.parametrize("fault", ["none", "exchange"])
def test_sharded_cell_on_four_virtual_devices(fault):
    code = SHARDED.format(tests=os.path.dirname(__file__), seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code, fault], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    want = "CORRECT True" if fault == "none" else "CORRECT False"
    assert want in p.stdout, p.stdout[-2000:]
