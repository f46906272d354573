"""Measured-autotune cache determinism (PR-4 contract, pinned).

`fused_chain(mode=None)` routes through the *in-process* measured-mode
cache only: the on-disk copy is written for inspection but never read back
unless REPRO_AUTOTUNE_CACHE_READ=1, so two identical runs in one process
make identical routing decisions regardless of what any previous run left
on disk."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.vector import VectorConfig
from repro.kernels import stencil


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """Isolated cache state: fresh disk path, empty in-process cache,
    READ unset, and no CI-matrix forced default mode (this test is about
    the auto-mode routing the matrix override would bypass)."""
    path = tmp_path / "chain_autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE_READ", raising=False)
    monkeypatch.setattr(autotune, "_MODE_CACHE", {})
    monkeypatch.setattr(autotune, "_DISK_CACHE_LOADED", False)
    prev = stencil.set_default_chain_mode(None)
    yield path
    stencil.set_default_chain_mode(prev)


def _chain():
    return (stencil.erode_stage(1),)


def _rng():
    # private stream: do not consume the session-scoped rng fixture (the
    # pre-existing suite's random data would shift)
    return np.random.default_rng(4321)


def _img(rng):
    return jnp.asarray(rng.integers(0, 256, (48, 64), dtype=np.uint8))


def _fake_disk_entry(path, chain, img, vc, mode):
    # entries must be sealed (schema version + checksum) or the validated
    # plan-table loader quarantines them — see test_plan_table.py
    key = autotune._cache_key(chain, img.shape, img.dtype, vc)
    entry = autotune.seal_entry(key, {"mode": mode, "times": {mode: 0.0}})
    path.write_text(json.dumps({key: entry}))


def test_same_run_twice_is_deterministic(cache_env):
    """The same chain measured then routed twice in one process: identical
    decisions both times (the cache entry, once written, is the single
    routing input — no re-measure, no disk consult)."""
    img, chain, vc = _img(_rng()), _chain(), VectorConfig(lmul=1)
    res = autotune.measure_chain(img, chain, vc=vc, n=1, persist=False)
    first = autotune.cached_chain_mode(chain, img.shape, img.dtype, vc)
    second = autotune.cached_chain_mode(chain, img.shape, img.dtype, vc)
    assert first == second == res["mode"]
    a = stencil.fused_chain(img, chain, vc=vc)
    b = stencil.fused_chain(img, chain, vc=vc)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_disk_readback_by_default(cache_env):
    """REPRO_AUTOTUNE_CACHE_READ unset: a persisted entry on disk must NOT
    leak into routing — the in-process cache stays empty and auto mode
    falls back to the halo heuristic (here: a pallas launch, not the "ref"
    plan the poisoned disk entry names)."""
    img, chain, vc = _img(_rng()), _chain(), VectorConfig(lmul=1)
    _fake_disk_entry(cache_env, chain, img, vc, "ref")
    assert autotune.cached_chain_mode(chain, img.shape, img.dtype, vc) is None
    assert stencil.count_pallas_calls(      # heuristic plan, not disk "ref"
        lambda x: stencil.fused_chain(x, chain, vc=vc), img) == 1


def test_disk_readback_opt_in(cache_env, monkeypatch):
    """REPRO_AUTOTUNE_CACHE_READ=1: the same disk entry IS honored (and a
    "ref"-routed auto call issues no pallas launch)."""
    img, chain, vc = _img(_rng()), _chain(), VectorConfig(lmul=1)
    _fake_disk_entry(cache_env, chain, img, vc, "ref")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_READ", "1")
    monkeypatch.setattr(autotune, "_DISK_CACHE_LOADED", False)
    assert autotune.cached_chain_mode(chain, img.shape, img.dtype, vc) == "ref"
    out = stencil.fused_chain(img, chain, vc=vc)
    assert stencil.count_pallas_calls(
        lambda x: stencil.fused_chain(x, chain, vc=vc), img) == 0
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(stencil.fused_chain(img, chain, vc=vc, mode="window")))


def test_in_process_entry_wins_over_disk(cache_env, monkeypatch):
    """Even with read-back enabled, an in-process measurement takes
    precedence over the disk copy (setdefault merge): the process's own
    decisions stay stable under a stale disk file."""
    img, chain, vc = _img(_rng()), _chain(), VectorConfig(lmul=1)
    res = autotune.measure_chain(img, chain, vc=vc, n=1, persist=False)
    _fake_disk_entry(cache_env, chain, img, vc,
                     "window" if res["mode"] != "window" else "streaming")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE_READ", "1")
    monkeypatch.setattr(autotune, "_DISK_CACHE_LOADED", False)
    assert autotune.cached_chain_mode(chain, img.shape, img.dtype,
                                      vc) == res["mode"]


def test_cached_chain_entry_exposes_times(cache_env):
    """cached_chain_entry returns the full measurement so benches can skip
    a re-measure when the cache already decided the chain (`run.py
    --quick` contract)."""
    img, chain, vc = _img(_rng()), _chain(), VectorConfig(lmul=1)
    assert autotune.cached_chain_entry(chain, img.shape, img.dtype, vc) is None
    res = autotune.measure_chain(img, chain, vc=vc, n=1, persist=False)
    entry = autotune.cached_chain_entry(chain, img.shape, img.dtype, vc)
    assert entry is not None and entry["mode"] == res["mode"]
    assert set(entry["times"]) >= {res["mode"]}
