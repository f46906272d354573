"""Mixture-of-Experts FFN: top-k token-choice routing with capacity, scatter
dispatch/combine, shared experts, and aux-free bias routing (DeepSeek-V3).

Sharding strategy (see sharding/rules.py):
  expert weights (E, D, F): E sharded over ("data","model") jointly when
  divisible (1 expert/chip for dsv3 on a 16x16 pod — pure EP, no weight
  gathering), falling back to "model" only (Arctic: 128 experts, 8/chip).
  Dispatch buffers x_e (E, C, D) shard the same way; the token->expert
  scatter and the combine gather become the EP all-to-alls under SPMD.

Rank computation is *grouped* (one group per sequence): the slot index of a
token inside its expert buffer is  base[group, expert] + local_rank, where
local_rank comes from a cumsum over the (unsharded) within-group axis and
`base` from an exclusive cumsum of the small (B, E) count matrix across
groups. This keeps every big cumsum local to a shard — no all-gather of the
(T*k, E) one-hot (which for DeepSeek-V3 train_4k would be 8.6 GB).

Routing styles:
  "softmax"  — softmax over logits, top-k probs as weights (Switch/Mixtral).
  "sigmoid"  — DeepSeek-V3: sigmoid scores, selection may add a
               non-trainable bias (aux-free load balancing), weights are the
               *unbiased* scores normalized over the selected k.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


from .layers import ACTIVATIONS, dense_init

Array = jax.Array


def init_moe(key, cfg) -> dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(key, 5)
    dt = cfg.param_dtype
    p = {
        "router": dense_init(ks[0], (d, e), dtype=jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (e, d, f), dtype=dt),
        "w_up": dense_init(ks[2], (e, d, f), dtype=dt),
        "w_down": dense_init(ks[3], (e, f, d), dtype=dt, scale=1.0 / math.sqrt(f * 2 * cfg.n_layers)),
    }
    if m.router_style == "sigmoid":
        p["router_bias"] = jnp.zeros((e,), jnp.float32)  # non-trainable, updated by train loop
    if m.n_shared:
        from .layers import init_mlp
        p["shared"] = init_mlp(ks[4], d, m.d_ff_shared * m.n_shared, style="glu", dtype=dt)
    return p


def _route(p, x: Array, m) -> tuple[Array, Array, dict]:
    """x (B, S, D) -> (weights (B,S,k) fp32, idx (B,S,k) int32, aux metrics)."""
    logits = x.astype(jnp.float32) @ p["router"]                   # (B, S, E)
    if m.router_style == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"] if "router_bias" in p else scores
        _, idx = jax.lax.top_k(jax.lax.stop_gradient(sel), m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        probs = scores / jnp.maximum(jnp.sum(scores, axis=-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        if m.norm_topk:
            w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    e = logits.shape[-1]
    # Switch-style load-balance aux loss + router z-loss (both cheap, fp32).
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=-2), axis=(0, 1))
    p_e = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(f_e / m.top_k * p_e)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    metrics = {"moe_aux": aux, "moe_z": z, "expert_load": f_e}
    return w, idx.astype(jnp.int32), metrics


def moe_ffn(p, x: Array, cfg, *, capacity_factor: float | None = None,
            hint=lambda a, *_: a) -> tuple[Array, dict]:
    """x (B, S, D) -> (out (B, S, D), metrics). Capacity-dropped tokens pass
    through with weight 0 (their residual path still carries them).

    Two implementations:
      * shard_map all-to-all EP (production path): one group per device,
        local scatter into (E, C, D) buckets, lax.all_to_all to the expert
        owners, local expert GEMMs, reverse all-to-all. Chosen when the
        token count and E divide the mesh (see _a2a_plan).
      * pjit grouped-scatter fallback (small/indivisible shapes, decode,
        unsharded tests).
    """
    mesh = getattr(hint, "mesh", None)
    plan = _a2a_plan(mesh, cfg, x.shape, capacity_factor) if mesh is not None else None
    if plan is not None:
        return _moe_ffn_a2a(p, x, cfg, plan)
    return _moe_ffn_scatter(p, x, cfg, capacity_factor=capacity_factor, hint=hint)


def _a2a_plan(mesh, cfg, xshape, capacity_factor):
    from repro.sharding import rules as _r
    m = cfg.moe
    B, S, D = xshape
    sizes = _r.mesh_axis_sizes(mesh)
    bdp = tuple(a for a in ("pod", "data") if a in sizes)         # batch axes
    n_b = math.prod(sizes[a] for a in bdp)
    n_s = sizes.get("model", 1)                                   # seq axis (SP)
    ep_total = sizes.get("data", 1) * n_s
    if m.n_experts % ep_total == 0 and ep_total > 1:
        a2a_axes: tuple = ("data", "model")
        n_ep = ep_total
    elif m.n_experts % n_s == 0 and n_s > 1:
        a2a_axes = ("model",)
        n_ep = n_s
    else:
        return None
    # Hidden states arrive in SP layout (B over pod/data, S over model) so
    # the shard_map boundary is free. Decode (S == 1) stays on the scatter
    # path (tiny and dropless there); indivisible shapes fall back too.
    if S == 1 or B % n_b or (S % n_s if S > 1 else 0):
        return None
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor
    L = (B // n_b) * (S // n_s)                                   # tokens/shard
    C = max(int(math.ceil(L * m.top_k / m.n_experts * cf)), 1)
    return {"mesh": mesh, "bdp": bdp, "a2a_axes": a2a_axes,
            "all_axes": bdp + (("model",) if n_s > 1 else ()),
            "L": L, "C": C, "n_ep": n_ep}


def _moe_ffn_a2a(p, x: Array, cfg, plan) -> tuple[Array, dict]:
    """shard_map expert-parallel MoE, operating directly on the SP
    activation layout (B over pod/data, S over model)."""
    from jax.sharding import PartitionSpec as P
    m = cfg.moe
    B, S, D = x.shape
    L, C = plan["L"], plan["C"]
    a2a = plan["a2a_axes"]
    E = m.n_experts

    def local_fn(xl, router, router_bias, wg, wu, wd):
        # xl (B_loc, S_loc, D); wg/wu (E_loc, D, F); wd (E_loc, F, D)
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        if router_bias is not None:
            pl["router_bias"] = router_bias
        w, idx, metrics = _route(pl, xl, m)                       # (B_loc,S_loc,k)
        idxf = idx.reshape(L * m.top_k)
        oh = jax.nn.one_hot(idxf, E, dtype=jnp.int32)
        ranks = jnp.cumsum(oh, axis=0) - oh
        slot = jnp.take_along_axis(ranks, idxf[:, None], axis=1)[:, 0]
        keep = slot < C
        slot_c = jnp.minimum(slot, C - 1)
        xf = xl.reshape(L, D)
        upd = jnp.where(keep[:, None], jnp.repeat(xf, m.top_k, axis=0), 0).astype(x.dtype)
        buf = jnp.zeros((E, C, D), x.dtype).at[idxf, slot_c].add(upd, mode="drop")
        # dispatch a2a: (E, C, D) -> (E_loc, n_ep * C, D)
        xe = jax.lax.all_to_all(buf, a2a, split_axis=0, concat_axis=1, tiled=True)
        act = ACTIVATIONS[m.act]
        h = act(jnp.einsum("ecd,edf->ecf", xe, wg)) * jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd)
        # combine a2a: back to (E, C, D)
        yb = jax.lax.all_to_all(ye, a2a, split_axis=1, concat_axis=0, tiled=True)
        y = yb[idxf, slot_c]                                      # (L*k, D)
        y = y * (w.reshape(L * m.top_k, 1) * keep[:, None]).astype(y.dtype)
        out = jnp.sum(y.reshape(L, m.top_k, D), axis=1).reshape(xl.shape)
        drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
        mets = jnp.stack([metrics["moe_aux"], metrics["moe_z"], drop])
        mets = jax.lax.pmean(mets, plan["all_axes"])
        load = jax.lax.pmean(metrics["expert_load"], plan["all_axes"])
        return out, mets, load

    ep_spec = P(a2a if len(a2a) > 1 else a2a[0], None, None)
    x_spec = P(plan["bdp"], "model", None)
    rb = p.get("router_bias")
    out, mets, load = jax.shard_map(
        local_fn, mesh=plan["mesh"],
        in_specs=(x_spec, P(None, None),
                  (P(None) if rb is not None else None), ep_spec, ep_spec, ep_spec),
        out_specs=(x_spec, P(), P()), check_vma=False,
    )(x, p["router"], rb, p["w_gate"], p["w_up"], p["w_down"])
    metrics = {"moe_aux": mets[0], "moe_z": mets[1], "moe_drop_frac": mets[2],
               "expert_load": load}
    if m.n_shared and "shared" in p:
        from .layers import apply_mlp
        out = out + apply_mlp(p["shared"], x, act=m.act, style="glu")
    return out, metrics


def _moe_ffn_scatter(p, x: Array, cfg, *, capacity_factor: float | None = None,
                     hint=lambda a, *_: a) -> tuple[Array, dict]:
    m = cfg.moe
    B, S, D = x.shape
    k, E = m.top_k, m.n_experts
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor
    C = max(int(math.ceil(S * k / E * cf)), 1)                     # per-group capacity

    w, idx, metrics = _route(p, x, m)                              # (B,S,k)

    # --- group-local slot assignment (group = sequence; GShard capacity) ---
    idxg = idx.reshape(B, S * k)
    ohg = jax.nn.one_hot(idxg, E, dtype=jnp.int32)                 # (B, S*k, E)
    ranks = jnp.cumsum(ohg, axis=1) - ohg                          # within-group rank
    slot = jnp.take_along_axis(ranks, idxg[:, :, None], axis=2)[:, :, 0]
    keep = slot < C
    metrics["moe_drop_frac"] = 1.0 - jnp.mean(keep.astype(jnp.float32))
    slot_c = jnp.minimum(slot, C - 1)

    # --- dispatch: group-local scatter, partitionable along B (no comm);
    #     the EP all-to-all is the dense (B,E,C,D)->(E,B*C,D) reshard. ---
    x_rep = jnp.repeat(x, k, axis=1)                               # (B, S*k, D)
    upd = jnp.where(keep[:, :, None], x_rep, 0).astype(x.dtype)
    b_iota = jnp.arange(B, dtype=jnp.int32)[:, None] * jnp.ones((1, S * k), jnp.int32)
    x_eg = jnp.zeros((B, E, C, D), x.dtype)
    x_eg = x_eg.at[b_iota, idxg, slot_c].add(upd, mode="drop")
    x_eg = hint(x_eg, "moe_group")                                 # (B:dp, E:model)
    x_e = x_eg.transpose(1, 0, 2, 3).reshape(E, B * C, D)
    x_e = hint(x_e, "moe_dispatch")                                # (E: data x model)

    # --- expert computation (E-sharded batch matmul) ---
    act = ACTIVATIONS[m.act]
    h = act(jnp.einsum("ecd,edf->ecf", x_e, p["w_gate"])) * jnp.einsum("ecd,edf->ecf", x_e, p["w_up"])
    h = hint(h, "moe_ffn")
    y_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    y_e = hint(y_e, "moe_dispatch")

    # --- combine: reverse reshard, group-local gather, weighted k-sum ---
    y_eg = y_e.reshape(E, B, C, D).transpose(1, 0, 2, 3)
    y_eg = hint(y_eg, "moe_group")
    y = y_eg[b_iota, idxg, slot_c]                                 # (B, S*k, D)
    y = y * (w.reshape(B, S * k, 1) * keep[:, :, None]).astype(y.dtype)
    out = jnp.sum(y.reshape(B, S, k, D), axis=2)

    if m.n_shared and "shared" in p:
        from .layers import apply_mlp
        out = out + apply_mlp(p["shared"], x, act=m.act, style="glu", hint=hint)
    return out, metrics
