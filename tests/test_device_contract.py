"""What keeps a CPU run from passing for a device run: kernels interpret
only on the CPU backend, a stage the TPU cannot lower raises through every
ladder, peaks exist only for known chips, the compile cache honors
``JAX_COMPILATION_CACHE_DIR`` and marks each compile request in a profiler
capture, and seeded frames are the same in every process."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.vector import VectorConfig
from repro.data.synthetic import ImageStream
from repro.kernels import stencil

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


@pytest.mark.parametrize("backend, want", [("cpu", True), ("tpu", False)])
def test_run_interpret_follows_backend(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert VectorConfig().run_interpret is want


def test_run_interpret_refuses_other_backends(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        VectorConfig().run_interpret
    assert VectorConfig(interpret=True).run_interpret     # explicit wins


@pytest.mark.parametrize("ladder", [None, ("streaming", "ref")])
def test_unlowerable_stage_raises_through_ladder(ladder):
    """A warp stage compiled for the TPU raises NotImplementedError before
    any lowering, and no ladder turns it into a silent chain_ref run."""
    from repro.core import faultinject

    img = jnp.zeros((48, 64), jnp.float32)
    m = np.array([[1.0, 0.0, 0.5], [0.0, 1.0, 0.5]])
    chain = (stencil.warp_affine_stage(m, shape=(48, 64)),)
    faultinject.clear_degradation_log()
    with pytest.raises(NotImplementedError, match="warp_affine"):
        stencil.fused_chain(img, chain, vc=VectorConfig(interpret=False),
                            mode="streaming", ladder=ladder)
    assert not faultinject.degradation_log()


def test_peaks_table_keyed_by_device_kind():
    sys.path.insert(0, ROOT)
    from benchmarks import common
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="cpu"):
        common.peaks("cpu")


def _run(code: str, **env) -> str:
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu", **env})
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.strip()


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(tmp_path, env_dir):
    code = """
        import jax
        from repro.core import jaxcache
        path = jaxcache.enable()
        print(path == jax.config.jax_compilation_cache_dir, path)
    """
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if env_dir else {}
    if not env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = ""
    ok, path = _run(code, **env).split(" ", 1)
    assert ok == "True"
    assert path == (str(tmp_path) if env_dir
                    else os.path.join(ROOT, ".jax_cache"))


def test_compile_requests_leave_marks(tmp_path):
    """Each compile request `jaxcache` counts leaves one instant
    `jaxcache.request` span in a profiler capture."""
    code = f"""
        import glob
        import jax, jax.numpy as jnp
        from jax.profiler import ProfileData
        from repro.core import jaxcache
        jaxcache.enable()
        x = jnp.ones(5)
        c0 = jaxcache.counts()["requests"]
        with jax.profiler.trace({str(tmp_path / "trace")!r}):
            jax.jit(lambda v: v * 3)(x).block_until_ready()
            jax.jit(lambda v: v - 2)(x).block_until_ready()
        n = jaxcache.counts()["requests"] - c0
        path, = glob.glob({str(tmp_path / "trace")!r} + "/**/*.xplane.pb",
                          recursive=True)
        marks = sum(e.name == "jaxcache.request"
                    for p in ProfileData.from_file(path).planes
                    for line in p.lines for e in line.events)
        print(n, marks)
    """
    n, marks = map(int, _run(code, JAX_COMPILATION_CACHE_DIR=str(
        tmp_path / "cache")).split())
    assert n >= 2 and marks == n


def test_image_stream_same_in_every_process():
    code = """
        import numpy as np
        from repro.data.synthetic import ImageStream
        x, y = ImageStream(res=(24, 40)).batch(3, split="train")
        print(int(np.asarray(x, np.int64).sum()), list(np.asarray(y)))
    """
    a = _run(code, PYTHONHASHSEED="1")
    b = _run(code, PYTHONHASHSEED="2")
    assert a == b
    x, y = ImageStream(res=(24, 40)).batch(3, split="train")
    assert x.shape == (3, 24, 40, 3) and x.dtype == jnp.uint8
    assert a == f"{int(np.asarray(x, np.int64).sum())} {list(np.asarray(y))}"
