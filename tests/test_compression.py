"""Gradient compression: quantization numerics + shard_map compressed psum."""
import jax.numpy as jnp
import numpy as np

from conftest import run_subprocess
from repro.optim.compression import compress_with_feedback, dequantize, quantize


def test_error_feedback_accumulates():
    g = jnp.asarray(np.random.default_rng(0).standard_normal(256), jnp.float32)
    res = jnp.zeros_like(g)
    # repeated identical gradients: EF means the *running* dequantized sum
    # tracks the true sum much better than independent quantization
    total_q = jnp.zeros_like(g)
    for i in range(16):
        q, s, res = compress_with_feedback(g, res)
        total_q = total_q + dequantize(q, s)
    err_ef = float(jnp.max(jnp.abs(total_q - 16 * g)))
    q1, s1 = quantize(g)
    err_naive = float(jnp.max(jnp.abs(16 * dequantize(q1, s1) - 16 * g)))
    assert err_ef <= err_naive + 1e-5


def test_compressed_psum_matches_mean():
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim.compression import compressed_psum
mesh = make_mesh((8,), ("data",))
g = jnp.asarray(np.random.default_rng(0).standard_normal((8, 64)), jnp.float32)
res = jnp.zeros_like(g)
def body(gl, rl):
    return compressed_psum(gl, rl, "data")
out, new_res = jax.shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
                             out_specs=(P("data"), P("data")),
                             check_vma=False)(g, res)
true_mean = jnp.mean(g, axis=0)
err = float(jnp.max(jnp.abs(out[0] - true_mean)))
scale = float(jnp.max(jnp.abs(g)) / 127.0)
assert err <= scale, (err, scale)
print("PSUM_OK", err)
""")
    assert "PSUM_OK" in out
