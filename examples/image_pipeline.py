"""Image-processing pipeline example: blur -> edge boost -> erode, at both
of the paper's vectorization rungs, with timing.

    PYTHONPATH=src python examples/image_pipeline.py
"""
import os, sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.core.vector import VectorConfig
from repro.cv import imgproc
from repro.data.synthetic import ImageStream
from repro.kernels import ref

img = ImageStream().image((1080, 1920))

def pipeline_ref(im):
    blur = ref.sep_filter2d_ref(im, ref.gaussian_kernel1d(5), ref.gaussian_kernel1d(5))
    sharp_k = jnp.asarray([[0, -1, 0], [-1, 5, -1], [0, -1, 0]], jnp.float32)
    edge = ref.filter2d_ref(blur, sharp_k)
    return imgproc.erode_vanherk(edge, 1)

out = pipeline_ref(img)
jax.block_until_ready(out)
t0 = time.perf_counter()
out = pipeline_ref(img)
jax.block_until_ready(out)
print(f"1080p blur->sharpen->erode: {time.perf_counter()-t0:.3f}s on CPU/XLA; "
      f"out {out.shape} {out.dtype}")

# Pallas path (interpret-mode correctness on a crop; real perf needs a TPU)
crop = img[:256, :512]
from repro.kernels import ops
a = ops.gaussian_blur(crop, 5, vc=VectorConfig(lmul=4))
b = ref.sep_filter2d_ref(crop, ref.gaussian_kernel1d(5), ref.gaussian_kernel1d(5))
print("pallas gaussian_blur matches oracle:",
      int(jnp.max(jnp.abs(a.astype(int) - b.astype(int)))) <= 1)

# Fused stencil pipeline: the same blur->sharpen->erode chain as ONE
# pallas_call over the whole batch (see EXPERIMENTS.md §Perf)
from repro.kernels import stencil
sharp_k = jnp.asarray([[0, -1, 0], [-1, 5, -1], [0, -1, 0]], jnp.float32)
chain = (stencil.gaussian_stage(5), stencil.filter_stage(sharp_k),
         stencil.erode_stage(1))
batch = jnp.stack([crop, crop])[..., None]            # (B, H, W, C)
t0 = time.perf_counter()
fused = stencil.fused_chain(batch, chain, vc=None)    # chain-aware autotune
jax.block_until_ready(fused)
n_calls = stencil.count_pallas_calls(
    lambda x: stencil.fused_chain(x, chain, vc=None), batch)
print(f"fused 3-stage chain on {tuple(batch.shape)}: "
      f"{time.perf_counter()-t0:.3f}s, {n_calls} kernel launch")
oracle = ref.chain_ref(batch, chain)
# u8 saturate_cast tolerance: XLA's mul+add vs fused-multiply-add codegen
# can differ by 1 ulp at .5 rounding boundaries
print("fused matches chain oracle (<=1):",
      int(jnp.max(jnp.abs(fused.astype(int) - oracle.astype(int)))) <= 1)
