"""Serving-throughput benchmark: `CvEngine.extract` over CIFAR-like frames.

Measures end-to-end images/sec through the fault-tolerant serving engine
(admission -> bucketing/padding -> batched ladder execution) at the
paper's 32x32 serving bucket, and the engine's overhead against calling
`pipeline.extract_features` directly on the same pre-batched frames —
the price of the robustness layer (admission checks, bucket grouping,
per-request Response assembly) when no fault fires.

`--sharded` adds the multi-device fan-out rows: batch-1024 `CvEngine`
serves through `serve.shard_dispatch.ShardDispatcher` over meshes of 1,
2, 4, ... of this process's devices (quick: 1 and all), all in ONE
process — a TPU host's chips belong to the first process that touches
them.  Rows are recorded under bench key "serve" with case
`serve_sharded_d<N>` (devices folded into the case so history matching
keys each device count separately).  To rehearse on the CPU, give the
process virtual devices before it starts
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Rows land under bench key "serve" in BENCH_results.json; the perf gate's
`--require-serve-sharded` flag asserts the batch-1024 sharded row exists
(the chaos-multi CI cell passes it); the other serve rows are
history-tracked but not gated.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.cv import PipelineConfig, pipeline
from repro.data.synthetic import ImageStream
from repro.serve.cv_engine import CvEngine

from .common import best_of, flush_results, print_table, record_result

BUCKET = (32, 32)
MAX_KP = 16
SHARD_BATCH = 1024


def _workload(n: int):
    imgs, _ = ImageStream(seed=3).batch(n, split="serve")
    return [np.asarray(f) for f in imgs]


def run(quick: bool = False) -> list[dict]:
    batches = (64,) if quick else (64, 256)
    rows = []
    for n in batches:
        work = _workload(n)
        eng = CvEngine(buckets=(BUCKET,), max_batch=64, max_kp=MAX_KP)

        def serve(_x=None, work=work, eng=eng):
            res = eng.extract(work)
            assert all(r.ok for r in res)
            return res

        def direct(_x=None, work=work):
            # same 64-frame batching policy as the engine, so the delta
            # isolates admission/bucketing/Response overhead, not batch shape
            outs = []
            for lo in range(0, len(work), 64):
                batch = np.stack(work[lo : lo + 64])
                feats = pipeline.extract_features(
                    batch, PipelineConfig(max_kp=MAX_KP, mode="streaming"))
                outs.append(np.asarray(feats["desc"]))
            return outs

        serve_s = best_of(serve, None, n=3)
        direct_s = best_of(direct, None, n=3)
        res = serve(None)
        row = {
            "batch": n,
            "case": "serve_extract",
            "resolution": f"{BUCKET[0]}x{BUCKET[1]}",
            "images_per_s": round(n / serve_s, 2),
            "serve_best_s": round(serve_s, 4),
            "direct_best_s": round(direct_s, 4),
            "engine_overhead_pct": round(100.0 * (serve_s - direct_s) / direct_s, 1),
            "plan": res[0].plan,
            "degraded": sum(r.degraded for r in res),
        }
        rows.append(row)
        record_result("serve", row)
    headers = ["batch", "images/s", "serve_s", "direct_s", "overhead%", "plan"]
    table = [
        [r["batch"], r["images_per_s"], r["serve_best_s"], r["direct_best_s"],
         r["engine_overhead_pct"], r["plan"]]
        for r in rows
    ]
    print_table("Serving throughput (CvEngine.extract, bucket 32x32)", headers, table)
    return rows


# ---------------------------------------------------------------------------
# sharded fan-out rows (multi-device, one process)
# ---------------------------------------------------------------------------

def _sharded_row(n_dev: int, work, quick: bool) -> dict:
    """Serve one batch-1024 workload through the dispatcher over the
    first `n_dev` devices."""
    from repro.launch.mesh import make_cv_mesh

    eng = CvEngine(buckets=(BUCKET,), max_batch=SHARD_BATCH,
                   max_kp=MAX_KP, mesh=make_cv_mesh(n_dev))
    eng.extract(work[:64])                  # compile pass (shapes warm)
    serve_s = best_of(lambda _x=None: eng.extract(work), None,
                      n=1 if quick else 2)
    res = eng.extract(work)
    assert all(r.ok for r in res), \
        f"{sum(not r.ok for r in res)} failed requests in sharded bench"
    d = eng.dispatcher
    return {
        "batch": SHARD_BATCH,
        "case": f"serve_sharded_d{n_dev}",
        "resolution": f"{BUCKET[0]}x{BUCKET[1]}",
        "devices": n_dev,
        "images_per_s": round(SHARD_BATCH / serve_s, 2),
        "serve_best_s": round(serve_s, 4),
        "plan": res[0].plan,
        "collective_batches": d.stats["collective_batches"] if d else 0,
        "redispatches": d.stats["redispatches"] if d else 0,
        "quarantined": len(d.health.quarantined()) if d else 0,
    }


def run_sharded(quick: bool = False) -> list[dict]:
    """batch-1024 serve over 1, 2, 4, ... of this process's devices
    (quick: 1 and all of them)."""
    n_all = len(jax.devices())
    counts = {1, n_all}
    if not quick:
        counts |= {1 << k for k in range(n_all.bit_length())}
    work = _workload(SHARD_BATCH)
    rows = []
    for n in sorted(c for c in counts if c <= n_all):
        row = _sharded_row(n, work, quick)
        rows.append(row)
        record_result("serve", row)
    headers = ["devices", "batch", "images/s", "serve_s", "plan",
               "collective", "redispatch"]
    table = [[r["devices"], r["batch"], r["images_per_s"],
              r["serve_best_s"], r["plan"], r["collective_batches"],
              r["redispatches"]] for r in rows]
    print_table("Sharded serving throughput (CvEngine + ShardDispatcher, "
                f"batch {SHARD_BATCH})", headers, table)
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--sharded", action="store_true",
                    help="run the multi-device batch-1024 fan-out rows "
                         "over this process's devices")
    args = ap.parse_args()
    if args.sharded:
        run_sharded(quick=args.quick)
    else:
        run(quick=args.quick)
    out = flush_results()
    if out:
        print(f"\nresults -> {out}")
