"""Chain driver — the public entry points of the fused stencil engine.

`fused_chain` resolves one call to an execution plan (explicit `mode=`,
the process default, the measured-autotune cache, or the halo heuristic),
normalizes the image to (N, H, W) planes, and runs the planned launch
through the degradation ladder.  `chained_launches` composes launches
across the `next_base` terminal-tap contract (pyramids).  The jitted
`_chain_planes` is the single Plan -> callable seam: it builds the
`plan.ChainGeom` and dispatches the executor (`exec_window` /
`exec_streaming`; `tiled2d` is the streaming executor with a column-tile
axis), while `exec_ref.chain_ref_planes` is the no-launch floor."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from .. import ref
from . import exec_ref, exec_streaming, exec_window, ir
from . import plan as plan_mod
from .ir import flat_weights, respec, spec_of
from .ladder import MODES, default_chain_mode, resolve_rungs, run_ladder
from .plan import build_chain_geom, chain_accumulated_halo

Array = jax.Array


def count_pallas_calls(fn, *args, **kwargs) -> int:
    """Number of pallas_call equations in fn's jaxpr (recursing into calls)."""
    def walk(jaxpr) -> int:
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += 1
            for v in eqn.params.values():
                if isinstance(v, jex_core.ClosedJaxpr):
                    n += walk(v.jaxpr)
                elif isinstance(v, jex_core.Jaxpr):
                    n += walk(v)
        return n
    return walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr)


@functools.partial(jax.jit, static_argnames=("spec", "vc", "stream", "tile_w"))
def _chain_planes(planes: Array, weights: tuple, spec: tuple,
                  vc, stream: bool = False,
                  tile_w: int | None = None) -> tuple:
    """(N, H, W) planes -> tuple of output bands (N, H_k, W_k): the whole
    chain in one pallas_call.

    Grid = (N / P, n_tiles, n_bands) where P is the plane block
    (`plan.plane_block`) and n_tiles the column-tile extent (1 unless
    `tile_w` splits the width — the tiled2d plan).  The geometry — input
    window from the exact backward row walk, per-tile column origins,
    gather-bound validation, streaming ring allocation, per-band store and
    crop rules — all comes from `plan.build_chain_geom`; this function is
    only the Plan -> executor seam."""
    stages = respec(spec, weights)
    if not vc.run_interpret:
        bad = sorted({s.op for s in stages} & exec_window.NO_TPU_LOWERING)
        if bad:
            raise NotImplementedError(
                f"fused_chain: stage(s) {bad} have no TPU lowering "
                "(exec_window.NO_TPU_LOWERING); run them with mode='ref'")
    geom = build_chain_geom(stages, planes.shape, planes.dtype, vc,
                            stream=stream, tile_w=tile_w)
    ex = exec_streaming if stream else exec_window
    return ex.execute(planes, stages, geom, vc)


def fused_chain(img: Array, stages, *, vc=None, mode: str | None = None,
                ladder=None, tile_w: int | None = None):
    """Run a stage chain over an image in ONE Pallas launch.

    img: (H, W), (H, W, C) or (B, H, W, C); u8 / f32 / bf16 carrier.
    vc: block width; None = chain-aware autotune (largest lmul whose
        accumulated-halo, band-count-aware working set fits VMEM —
        streaming/tiled2d modes charge the smaller ring-carry footprint).
    mode: execution plan — "streaming" (row-carry rings; default for
        chains with row halo), "tiled2d" (streaming plus a column-tile
        grid axis: per-tile windows, rings and column origins, tile width
        autotuned alongside lmul via `plan.pick_tile_plan`), "window"
        (overlapping-window recompute), "ref" (staged `ref.chain_ref`,
        no Pallas launch), or None/"auto" (the `autotune.measure_chain`
        cached winner for this chain + shape + dtype + vc + backend, else
        the halo heuristic).  All Pallas plans are bit-identical for
        every stencil stage; "ref" agrees within the repo's oracle
        tolerance (u8/bf16 float-accumulating stages may land a .5
        rounding tie one ulp apart — the package-docstring
        border-semantics caveat), and fractional-coordinate gathers carry
        the documented coordinate-ulp caveat across *any* two
        differently-fused programs.
    tile_w: tiled2d only — explicit tile width (input-resolution columns;
        must divide by the chain's column stride product).  None
        autotunes it; >= the image width means one tile (the exact
        streaming geometry).

    Returns a single array when the chain ends with one live band, else a
    tuple of arrays (one per band — e.g. a Gaussian ladder's scales plus a
    pyrDown next-octave base, or a Sobel dx/dy pair), each with the
    geometry its band's stride history implies.

    Planes smaller than the chain's accumulated halo fall back to the
    `ref.chain_ref` oracle (identical semantics, no Pallas launch): the
    fused window would be mostly replicated padding, so there is no VMEM
    traffic to save — and the guard keeps the window planner out of the
    degenerate pad-dominated regime entirely.

    ladder: degradation ladder — an ordered tuple of rungs (subset of
        `DEGRADATION_LADDER`); when the resolved plan (or any later rung)
        fails with anything but a ValueError (chain misconfiguration
        always surfaces), execution degrades to the next rung and a
        structured `core.faultinject` degradation event is recorded.  The
        final rung's failure raises.  None = the process default
        (`set_default_ladder`), which itself defaults to no ladder — the
        pre-ladder raise-on-failure contract.
    """
    from repro.core import faultinject

    stages = tuple(stages)
    if not stages:
        return img
    if img.ndim not in (2, 3, 4):
        raise ValueError(f"fused_chain: unsupported rank {img.ndim}")
    ph_in, pw_in = chain_accumulated_halo(stages)
    h_in, w_in = ((img.shape[-2], img.shape[-1]) if img.ndim == 2
                  else (img.shape[-3], img.shape[-2]))
    if h_in <= ph_in or w_in <= pw_in:
        # structural chain_ref fallback: recorded so serving can tell a
        # pad-dominated plane took the no-launch route by design
        faultinject.record_degradation(
            stage="fused_chain",
            from_plan=mode or default_chain_mode() or "auto",
            to_plan="ref",
            reason=f"planes<=halo ({h_in}x{w_in} vs {ph_in}x{pw_in}): "
                   "structural chain_ref fallback",
            detail=f"{img.shape}|{jnp.dtype(img.dtype).name}")
        return ref.chain_ref(img, stages)
    if mode in (None, "auto"):
        if default_chain_mode() is not None:    # CI mode-matrix override
            mode = default_chain_mode()
        else:
            from repro.core.autotune import cached_chain_mode
            mode = cached_chain_mode(stages, img.shape, img.dtype, vc)
            if mode is None:
                # heuristic: carry rows whenever there is row halo to carry
                mode = "streaming" if ph_in > 0 else "window"
    if mode not in MODES:
        raise ValueError(f"fused_chain: unknown mode {mode!r} (expected "
                         f"one of {MODES} or None)")
    if tile_w is not None and mode != "tiled2d":
        raise ValueError(f"fused_chain: tile_w= only applies to "
                         f"mode='tiled2d', not {mode!r}")
    rungs = resolve_rungs(mode, ladder)

    def _run(plan: str):
        if plan == "ref":
            return exec_ref.chain_ref_planes(img, flat_weights(stages),
                                             spec_of(stages))
        stream = plan in ("streaming", "tiled2d")
        faultinject.maybe_raise("lowering_error", site=f"fused_chain:{plan}")
        vck, tw = vc, None
        if plan == "tiled2d":
            if vck is None:
                tw, vck = plan_mod.pick_tile_plan(stages, w_in,
                                                  in_dtype=img.dtype)
            if tile_w is not None:
                tw = tile_w
            elif vc is not None:
                tw = plan_mod.pick_tile_w(stages, w_in, img.dtype, vck)
        if vck is None:
            vck = plan_mod.pick_chain_lmul(stages, w_in, in_dtype=img.dtype,
                                           streaming=stream)

        spec, weights = spec_of(stages), flat_weights(stages)
        if img.ndim == 2:
            outs = _chain_planes(img[None], weights, spec, vck,
                                 stream=stream, tile_w=tw)
            outs = tuple(o[0] for o in outs)
        elif img.ndim == 3:                # (H, W, C) -> planes (C, H, W)
            planes = jnp.moveaxis(img, -1, 0)
            outs = _chain_planes(planes, weights, spec, vck,
                                 stream=stream, tile_w=tw)
            outs = tuple(jnp.moveaxis(o, 0, -1) for o in outs)
        else:                              # (B, H, W, C) -> planes (B*C, H, W)
            B, H, W, C = img.shape
            planes = jnp.moveaxis(img, -1, 1).reshape(B * C, H, W)
            outs = _chain_planes(planes, weights, spec, vck,
                                 stream=stream, tile_w=tw)
            outs = tuple(jnp.moveaxis(o.reshape(B, C, *o.shape[1:]), 1, -1)
                         for o in outs)
        return outs[0] if len(outs) == 1 else outs

    return run_ladder(rungs, _run, stage="fused_chain",
                      detail=f"{img.shape}|{jnp.dtype(img.dtype).name}")


def chained_launches(img: Array, chains, *, vc=None,
                     mode: str | None = None, ladder=None) -> tuple[list, list]:
    """Cross-launch chain composition: one `fused_chain` launch per link,
    where link k+1 consumes link k's final output band (the `next_base`
    terminal strided tap, see `ir.validate_next_base`) as its input — an
    N-link pyramid lowers to exactly N `pallas_call`s, with band state,
    autotune keys and coordinate origins handed off *across* launches
    instead of within one.

    Every non-final link must satisfy the next_base contract; its carry
    band is removed from that link's returned tuple (it is the next
    launch's input, not a pyramid product).  Each launch autotunes
    independently: `vc=None` re-picks the block width for the link's
    (shrinking) plane geometry, and `mode=None` consults the measured-mode
    cache under the link's own shape key (`autotune.measure_pyramid` warms
    one entry per link).  Links whose planes fall below their chain's
    accumulated halo run the `ref.chain_ref` fallback (identical
    semantics, no launch) — the pyramid-tail rule.

    Returns ``(outs, scales)``: ``outs[k]`` is link k's output-band tuple
    and ``scales[k]`` the (row, col) base-coordinate scale of link k —
    pixel (y, x) of link k sits at base-image coordinates
    ``(y * scales[k][0], x * scales[k][1])``, exact because strided taps
    decimate on image-aligned (even) coordinates and every output band is
    cropped to image origin."""
    chains = tuple(tuple(c) for c in chains)
    if not chains:
        raise ValueError("chained_launches: need at least one chain")
    outs_all, scales = [], []
    base = img
    sy = sx = 1
    for k, stages in enumerate(chains):
        last = k == len(chains) - 1
        if not last:
            ir.validate_next_base(stages)
        outs = fused_chain(base, stages, vc=vc, mode=mode, ladder=ladder)
        if not isinstance(outs, tuple):
            outs = (outs,)
        scales.append((sy, sx))
        if last:
            outs_all.append(outs)
        else:
            outs_all.append(outs[:-1])
            base = outs[-1]
            st = tuple(stages[-1].stride)
            sy, sx = sy * st[0], sx * st[1]
    return outs_all, scales
