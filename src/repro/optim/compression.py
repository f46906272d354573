"""int8 error-feedback gradient compression for DP all-reduce.

At 1000+-node scale the DP gradient all-reduce is ICI/DCN-bound; 4x byte
reduction (fp32 -> int8 + fp32 scale) with error feedback (Seide et al.;
1-bit SGD lineage) keeps convergence while quartering reduce traffic.

compressed_psum runs inside shard_map: quantize locally -> psum the int8
payload (as int32 accumulator to avoid overflow) -> dequantize; the
quantization residual is returned for the caller to fold into the next
step's gradient (error feedback). Numerics are validated in
tests/test_compression.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


Array = jax.Array


def quantize(g: Array) -> tuple[Array, Array]:
    """Per-tensor symmetric int8. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(g))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale


def compress_with_feedback(g: Array, residual: Array) -> tuple[Array, Array, Array]:
    """(q, scale, new_residual): quantize g + residual, keep the error."""
    corrected = g + residual
    q, scale = quantize(corrected)
    new_residual = corrected - dequantize(q, scale)
    return q, scale, new_residual


def compressed_psum(g: Array, residual: Array, axis_name) -> tuple[Array, Array]:
    """Inside shard_map: error-feedback int8 all-reduce of g over axis_name.

    Uses a *shared* scale (pmax of local scales) so the integer payloads are
    summable on the wire. XLA today lowers the psum at int32 width — the
    4x wire saving needs hardware int8 collectives (noted in DESIGN §5);
    `bf16_psum` below is the XLA-native 2x variant. Numerics (quantization
    + error feedback) are exactly what the int8 wire format would compute.

    Returns (mean-reduced fp32 gradient, new local residual)."""
    corrected = g + residual
    local_amax = jnp.max(jnp.abs(corrected))
    scale = jax.lax.pmax(jnp.maximum(local_amax, 1e-12), axis_name) / 127.0
    q = jnp.clip(jnp.round(corrected / scale), -127, 127).astype(jnp.int8)
    new_res = corrected - q.astype(jnp.float32) * scale
    total = jax.lax.psum(q.astype(jnp.int32), axis_name).astype(jnp.float32) * scale
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return total / n, new_res


def bf16_psum(g: Array, axis_name) -> Array:
    """2x wire reduction, XLA-native: mean-psum in bfloat16."""
    total = jax.lax.psum(g.astype(jnp.bfloat16), axis_name).astype(jnp.float32)
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis_name)
    return total / n


def make_compressed_allreduce(mesh, axis_name="data"):
    """Returns allreduce(tree, residuals) -> (means, new_residuals),
    a drop-in for a DP gradient mean over `axis_name`."""
    from jax.sharding import PartitionSpec as P

    def one(g, r):
        def body(gl, rl):
            return compressed_psum(gl, rl, axis_name)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(axis_name), P(axis_name)),
                             out_specs=(P(axis_name), P(axis_name)),
                             check_vma=False)(g, r)

    def allreduce(tree, residuals):
        out = jax.tree.map(one, tree, residuals)
        means = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        res = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return means, res

    return allreduce
