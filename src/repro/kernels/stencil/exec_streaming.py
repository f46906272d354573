"""Streaming executor — row-carry rings, shared by the `streaming` (one
full-width tile) and `tiled2d` (column-tiled) plans.

The band (row) axis of the grid iterates innermost/sequentially, so VMEM
scratch persists across the steps of one (plane block, tile) pair and is
re-primed whenever the tile or plane-block axis advances — per-tile ring
state with no cross-tile bleed.  Step 0 of each tile runs the window pass
(`exec_window.window_pass(prime=True)`), which both computes the first
band and fills every ring with the tail rows of each band's stream;
steps i>0 run the stream pass below, which computes only each stage's
*new* rows from (ring ++ upstream new rows) and rotates the rings — so
redundant halo recompute scales with neither chain depth nor tile count."""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
import jax.numpy as jnp

from .exec_window import (_apply_grad_pair, _apply_sobel, _materialize,
                          apply_stage, launch, read_rows, split_refs,
                          store_bands, window_pass)

Array = jax.Array


def stream_pass(x_ref, ring_refs, wts_k, plan, carrier, interp, band_i,
                tile_j, splan):
    """One streaming step: compute each stage's new rows from its carried
    ring plus the upstream stage's new rows; returns the new-rows band
    list.  `splan` is ``(mult0, r0, sstages)`` with per-stage ``(sin_lo,
    sin_r, ring_rows, d_rows, op_rids, d_rids, smeta)``."""
    mult0, r0, sstages = splan
    # each live band is represented by its `mult` NEW rows at the
    # current stage's input; band 0 starts as the window's fresh tail
    news = [read_rows(x_ref, r0 - mult0, r0)]
    for k, (op, static, mode, tap, (ph, pw), _wmeta) in enumerate(plan):
        sin_lo, sin_r, ring_rows, d_rows, op_rids, d_rids, smeta = \
            sstages[k]
        wts = wts_k[k]

        def buf_of(src, rid, sin_lo=sin_lo, sin_r=sin_r,
                   ring_rows=ring_rows):
            # stage body input = carried ring rows ++ upstream new rows
            # (stage 0 slices the window: its history is DMA-resident)
            if sin_lo is not None:
                return read_rows(x_ref, sin_lo, sin_lo + sin_r)
            if ring_rows == 0:
                return src
            buf = jnp.concatenate([ring_refs[rid][...], src], axis=-2)
            ring_refs[rid][...] = buf[..., buf.shape[-2] - ring_rows:, :]
            return buf

        def delayed(bs, d_rids=d_rids, d_rows=d_rows):
            # pass-through bands lag by the stage halo (d_rows FIFO) so
            # the band state stays row-aligned with the tapped output
            if d_rows == 0:
                return list(bs)
            out = []
            for b, rid in zip(bs, d_rids):
                db = jnp.concatenate([ring_refs[rid][...], b], axis=-2)
                ring_refs[rid][...] = db[..., db.shape[-2] - d_rows:, :]
                out.append(db[..., :b.shape[-2], :])
            return out

        if mode == "emit":
            buf = buf_of(news[-1], op_rids[0] if op_rids else None)
            dx, dy = _apply_sobel(buf, interp=interp)
            news = delayed(news[:-1]) + [dx, dy]
        elif mode == "reduce":
            news = news[:-2] + [_apply_grad_pair(news[-2], news[-1],
                                                 carrier)]
        elif mode == "tap":
            buf = buf_of(news[tap], op_rids[0] if op_rids else None)
            new = apply_stage(op, buf, wts, static, news[tap].dtype, smeta,
                              band_i, tile_j, interp)
            if interp:
                new = _materialize(new)
            news = delayed(news) + [new]
        else:
            news = [apply_stage(op, buf_of(b, op_rids[j] if op_rids else None),
                                wts, static, b.dtype, smeta, band_i, tile_j,
                                interp)
                    for j, b in enumerate(news)]
    return news


def streaming_kernel(x_ref, *refs, plan, carrier, interp, n_out, splan,
                     n_ring, store_slices):
    """Streaming plan kernel: band 0 of every (plane block, tile) primes
    the rings via the window pass; later bands run the stream pass."""
    wts_k, out_refs, ring_refs = split_refs(refs, plan, n_out, n_ring)
    band_i, tile_j = pl.program_id(2), pl.program_id(1)

    @pl.when(band_i == 0)
    def _():
        bands = window_pass(x_ref, splan[1], ring_refs, wts_k, plan, carrier,
                            interp, band_i, tile_j, splan=splan, prime=True)
        store_bands(out_refs, bands, store_slices)

    @pl.when(band_i != 0)
    def _():
        news = stream_pass(x_ref, ring_refs, wts_k, plan, carrier, interp,
                           band_i, tile_j, splan)
        store_bands(out_refs, news, store_slices)


def execute(planes: Array, stages, geom, vc) -> tuple:
    """`ChainGeom -> callable` for the streaming/tiled2d plans.  A chain
    whose carry plan allocates no rings (halo-free) degenerates to the
    window kernel — the window pass IS minimal there."""
    if geom.splan is None:
        from . import exec_window
        return exec_window.execute(planes, stages, geom, vc)
    store_slices = tuple((loc0, store_w)
                         for _, _, store_w, loc0, _, _, _ in geom.outs)
    kernel = functools.partial(streaming_kernel, plan=geom.plan,
                               carrier=planes.dtype, interp=vc.run_interpret,
                               n_out=len(geom.outs), splan=geom.splan,
                               n_ring=len(geom.ring_shapes),
                               store_slices=store_slices)
    return launch(planes, stages, geom, vc, kernel)
