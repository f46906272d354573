"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the sizes the chip runs (1080p frames, k=250).

Interpret mode cannot see what Mosaic refuses (misaligned blocks, packed
integer ops, strided value slices, gathers); these compiles can, in a
couple of seconds each, without a chip.  The topology is described inside
a fixture: only one process may hold the TPU compiler's library, so it is
never loaded while a module is imported.  Nothing here runs a kernel.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.vector import VectorConfig
from repro.cv import features
from repro.kernels import bow as kbow
from repro.kernels import gbdt as kgbdt
from repro.kernels import stencil
from repro.kernels.stencil import driver
from repro.kernels.stencil import plan as splan
from repro.kernels.stencil.ir import flat_weights, spec_of

HW = (1080, 1920)
TPU = VectorConfig(interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_chain(sharding, stages, planes, dtype, mode="streaming"):
    """Lower + compile one fused-chain launch exactly as `fused_chain`
    plans it (autotuned block width), for the described chip."""
    stages = tuple(stages)
    stream = mode in ("streaming", "tiled2d")
    vc = splan.pick_chain_lmul(stages, HW[1], in_dtype=dtype, base=TPU,
                               streaming=stream)
    w = tuple(_sds(sharding, x.shape, x.dtype) for x in flat_weights(stages))
    return driver._chain_planes.lower(
        _sds(sharding, (planes,) + HW, dtype), w, spec=spec_of(stages),
        vc=vc, stream=stream).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_serving_octave_chain_compiles(one_chip):
    """The served single-octave SIFT ladder (f32, one plane per frame)."""
    chain = features.octave_chain(with_next_base=False)
    _assert_kernel(_compile_chain(one_chip, chain, 1, jnp.float32))


def test_acceptance_chain_u8_c3_compiles(one_chip):
    """gauss -> erode -> thresh on a u8 C3 frame: packed-int carriers
    widen through i32 (no packed min/max or u8<->f32 casts on the VPU)."""
    chain = (stencil.gaussian_stage(5), stencil.erode_stage(1),
             stencil.threshold_stage(127.5))
    _assert_kernel(_compile_chain(one_chip, chain, 3, jnp.uint8))


def test_bow_quantize_hist_k250_compiles(one_chip):
    d = _sds(one_chip, (8, 32, 128), jnp.float32)
    v = _sds(one_chip, (8, 32), jnp.bool_)
    c = _sds(one_chip, (250, 128), jnp.float32)
    compiled = jax.jit(lambda d, v, c: kbow.bow_quantize_hist(
        d, v, c, vc=TPU)).lower(d, v, c).compile()
    _assert_kernel(compiled)


def test_linear_score_compiles(one_chip):
    h = _sds(one_chip, (8, 250), jnp.float32)
    w = _sds(one_chip, (10, 250), jnp.float32)
    b = _sds(one_chip, (10,), jnp.float32)
    compiled = jax.jit(lambda h, w, b: kbow.linear_score(
        h, w, b, vc=TPU)).lower(h, w, b).compile()
    _assert_kernel(compiled)


def test_gbdt_score_compiles(one_chip):
    T, depth, C = 64, 4, 10
    args = (_sds(one_chip, (8, 250), jnp.float32),
            _sds(one_chip, (T, depth), jnp.int32),
            _sds(one_chip, (T, depth), jnp.float32),
            _sds(one_chip, (T, 2 ** depth, C), jnp.float32),
            _sds(one_chip, (C,), jnp.float32))
    compiled = jax.jit(lambda *a: kgbdt.gbdt_score(*a, vc=TPU)).lower(
        *args).compile()
    _assert_kernel(compiled)
