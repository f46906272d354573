"""Block-width autotuner: the paper's m8-ceiling rule as a VMEM budget."""
import jax.numpy as jnp
import numpy as np

from repro.core.autotune import (chain_working_set, erode_working_set,
                                 filter2d_working_set, pick_lmul,
                                 pick_tile_plan)
from repro.core.vector import VectorConfig
from repro.kernels import stencil


def test_monotone_in_width():
    """Wider images -> working set grows -> picked lmul never increases."""
    prev = 99
    for w in (1920, 3840, 7680, 15360, 30720):
        l = pick_lmul(filter2d_working_set(w, 13)).lmul
        assert l <= prev
        prev = l


def test_widening_lowers_ceiling():
    """u8->f32 widening (the paper's m4-vs-m8 point): at the same geometry
    the widened filter kernel caps at a lower/equal lmul than u8 erosion."""
    for w in (3840, 7680, 15360):
        l_filter = pick_lmul(filter2d_working_set(w, 13)).lmul
        l_erode = pick_lmul(erode_working_set(w, 3)).lmul
        assert l_filter <= l_erode


# (width, ksize) at which no full-width u8 band fits the budget; a v5e
# compile bears out (7680, 13) in test_tpu_compile's
# test_wide_13x13_filter_compiles_in_column_tiles, and 15260 wide is not a
# lane multiple, which Mosaic refuses at any block
NO_FULL_WIDTH_BAND = {(7680, 13), (15260, 3), (15260, 7), (15260, 13)}


def test_picked_lmul_fits_budget():
    for w in (1920, 3840, 7680, 15260):
        for k in (3, 7, 13):
            ws = filter2d_working_set(w, k)
            vc = pick_lmul(ws)
            if (w, k) in NO_FULL_WIDTH_BAND:
                # the smallest block is returned, and a column tile fits
                assert vc.lmul == 1 and ws.bytes(vc) > vc.vmem_budget
                stages = (stencil.filter_stage(np.ones((k, k), np.float32)),)
                tw, vc_t = pick_tile_plan(stages, w)
                assert tw is not None and chain_working_set(
                    stages, tw, streaming=True).bytes(vc_t) <= vc_t.vmem_budget
                continue
            assert ws.bytes(vc) <= vc.vmem_budget
            # and the next lmul up would not fit (or is already max)
            if vc.lmul < 8:
                assert ws.bytes(vc.with_lmul(vc.lmul * 2)) > vc.vmem_budget
