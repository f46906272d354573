"""VectorConfig — the paper's LMUL register-grouping knob, mapped to TPU.

RVV 0.7.1 lets one instruction operate on a *block* of 1/2/4/8 vector
registers (LMUL). The paper's optimization is exactly "switch OpenCV's
universal intrinsics from m1 to m4". On TPU the analogous granularity is
the number of native (sublane, 128-lane) VREG tiles a Pallas kernel
processes per grid step: `lmul` scales the BlockSpec tile, amortizing
grid-step/DMA-issue overhead against VMEM footprint.

The paper's reason to stop at m4 — u8->u16/u32 widening doubles register
use, and m4 widened becomes m8, the ISA maximum — maps to the VMEM budget
rule in `repro.core.autotune`: pick the largest lmul whose *widened*
working set still fits VMEM.
"""
from __future__ import annotations

from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp

# native sublane count per VREG by element width (8 sublanes of 4-byte lanes)
_SUBLANES = {1: 32, 2: 16, 4: 8}

LANE = 128          # TPU vector lanes
# the planner's per-kernel VMEM budget; a v5e core has 128 MiB of VMEM,
# and Mosaic refuses only what exceeds that
VMEM_BYTES = 16 * 2**20


def sublanes(dtype) -> int:
    return _SUBLANES[jnp.dtype(dtype).itemsize]


@dataclass(frozen=True)
class VectorConfig:
    """Block-width configuration for all kernels in repro.kernels."""
    lmul: int = 4                  # {1, 2, 4, 8}: native tiles per grid step
    lane: int = LANE
    base_rows: int = 8             # fp32 sublanes; dtype packing scales this
    vmem_budget: int = VMEM_BYTES
    interpret: bool | None = None  # None = auto: interpret on CPU only

    def rows(self, dtype=jnp.float32) -> int:
        """Tile rows for `dtype` at this lmul (sublane packing x lmul)."""
        return sublanes(dtype) * self.lmul

    def cols(self, mult: int = 1) -> int:
        return self.lane * mult

    def tile_bytes(self, dtype=jnp.float32, mult: int = 1) -> int:
        return self.rows(dtype) * self.cols(mult) * jnp.dtype(dtype).itemsize

    @property
    def run_interpret(self) -> bool:
        """Compile for the TPU, or interpret on the CPU backend.  Any other
        backend is an error: the kernels are written for Mosaic, and an
        interpreted run there would time the interpreter, not the device."""
        if self.interpret is not None:
            return self.interpret
        backend = jax.default_backend()
        if backend == "tpu":
            return False
        if backend == "cpu":
            return True
        raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                           f"{backend!r}: use a TPU, or the CPU to interpret")

    def with_lmul(self, lmul: int) -> "VectorConfig":
        return replace(self, lmul=lmul)


# The paper's ladder: SeqVector == stock universal intrinsics (one native
# register / tile per op); Optim == 4-register blocks.
SEQ_VECTOR = VectorConfig(lmul=1)
OPTIM = VectorConfig(lmul=4)
DEFAULT = OPTIM
