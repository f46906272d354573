"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached, at the sizes the chip runs (1080p and 4K frames, k=250).

Interpret mode cannot see what Mosaic refuses (misaligned blocks, packed
integer ops, strided value slices, gathers); these compiles can, in a
couple of seconds each, without a chip.  The topology is described inside
a fixture: only one process may hold the TPU compiler's library, so it is
never loaded while a module is imported.  Nothing here runs a kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
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
HW_4K = (2160, 3840)
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


def _compile_chain(sharding, stages, planes, dtype, mode="streaming", *,
                   hw=HW, vc=None, compiler_options=None):
    """Lower + compile one fused-chain launch exactly as `fused_chain`
    plans it (autotuned block width, and column tile under tiled2d), for
    the described chip; `vc` fixes the block width instead."""
    stages = tuple(stages)
    stream = mode in ("streaming", "tiled2d")
    tw = None
    if vc is None and mode == "tiled2d":
        tw, vc = splan.pick_tile_plan(stages, hw[1], in_dtype=dtype, base=TPU)
    elif vc is None:
        vc = splan.pick_chain_lmul(stages, hw[1], in_dtype=dtype, base=TPU,
                                   streaming=stream)
    w = tuple(_sds(sharding, x.shape, x.dtype) for x in flat_weights(stages))
    return driver._chain_planes.lower(
        _sds(sharding, (planes,) + hw, dtype), w, spec=spec_of(stages),
        vc=vc, stream=stream, tile_w=tw).compile(
            compiler_options=compiler_options)


def _limit(kib):
    return {"xla_tpu_scoped_vmem_limit_kib": kib}


def _filter_erode(ksize=5, erode=True):
    """filter2D 5x5 -> 3x3 erode, the filter4k_u8c1 cell's served chain
    (or another kernel size, or the filter2D alone)."""
    k = np.random.default_rng(0).uniform(-3, 10, (ksize, ksize))
    f = stencil.filter_stage((k / abs(k.sum())).astype(np.float32))
    return (f, stencil.erode_stage(1)) if erode else (f,)


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


@pytest.mark.parametrize("planes", [16, 1])
def test_served_filter_chain_u8_4k_compiles(one_chip, planes):
    """The filter task's 4K u8 C1 batch (16 frames) and a single frame.
    When the planner left out the filter2D kernel rows' slices it chose
    128-row bands, and Mosaic refused the 16-plane launch (16.78 MiB of
    scoped VMEM against a 16 MiB limit)."""
    _assert_kernel(_compile_chain(one_chip, _filter_erode(), planes,
                                  jnp.uint8, hw=HW_4K))


def test_planner_charge_covers_the_served_filter_compile(one_chip):
    """Held to a scoped-VMEM limit of just the bytes the planner charges
    for the 16-plane 4K u8 chain, Mosaic still compiles it: the count is
    at least what the compile needs."""
    chain = _filter_erode()
    vc = splan.pick_chain_lmul(chain, HW_4K[1], in_dtype=jnp.uint8, base=TPU,
                               streaming=True)
    charged = splan.chain_working_set(chain, HW_4K[1], jnp.uint8,
                                      streaming=True).bytes(vc)
    assert splan.plane_block(chain, HW_4K[1], 16, vc, jnp.uint8,
                             streaming=True) == 1
    _assert_kernel(_compile_chain(
        one_chip, chain, 16, jnp.uint8, hw=HW_4K,
        compiler_options=_limit(charged // 1024)))


def test_planner_charge_covers_a_13x13_filter_compile(one_chip):
    """The served chain's form with a 13x13 kernel, 16 planes at 4K, held
    to the bytes the planner charges for the block it picks (32-row
    bands): Mosaic holds more per kernel row from 13 rows on, and the
    count has to follow."""
    chain = _filter_erode(13)
    vc = splan.pick_chain_lmul(chain, HW_4K[1], in_dtype=jnp.uint8, base=TPU,
                               streaming=True)
    charged = splan.chain_working_set(chain, HW_4K[1], jnp.uint8,
                                      streaming=True).bytes(vc)
    assert vc.lmul == 1 and charged <= vc.vmem_budget
    _assert_kernel(_compile_chain(one_chip, chain, 16, jnp.uint8, hw=HW_4K,
                                  compiler_options=_limit(charged // 1024)))


def test_wide_13x13_filter_compiles_in_column_tiles(one_chip):
    """13x13 u8 filter2D 7680 wide: no full-width band fits, and Mosaic
    refuses even the smallest (32 rows), as the planner says; the column
    tile and block width `pick_tile_plan` picks compile within the bytes
    the planner charges for them."""
    chain, hw = _filter_erode(13, erode=False), (256, 7680)
    small = TPU.with_lmul(1)
    assert splan.chain_working_set(chain, hw[1], jnp.uint8, streaming=True
                                   ).bytes(small) > small.vmem_budget
    with pytest.raises(Exception, match="scoped vmem limit"):
        _compile_chain(one_chip, chain, 1, jnp.uint8, hw=hw, vc=small)
    tw, vc = splan.pick_tile_plan(chain, hw[1], in_dtype=jnp.uint8, base=TPU)
    charged = splan.chain_working_set(chain, tw, jnp.uint8,
                                      streaming=True).bytes(vc)
    assert tw is not None and charged <= vc.vmem_budget
    _assert_kernel(_compile_chain(one_chip, chain, 1, jnp.uint8, "tiled2d",
                                  hw=hw, compiler_options=_limit(
                                      charged // 1024)))


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
