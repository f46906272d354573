"""Benchmark entry point: `python -m benchmarks.run [--quick]`.

Runs one harness per paper table (T1–T3 filter2D, T4–T6 erosion,
T7–T9 BoW+SVM), the block-width (lmul) ladder, and summarizes the
dry-run roofline table (§Roofline) if artifacts exist.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--mode", default="both",
                    choices=["both", "streaming", "window"],
                    help="fused-chain execution plan(s) to time "
                         "(make bench-quick MODE=...)")
    ap.add_argument("--only", default=None,
                    choices=[None, "filter2d", "erode", "bow", "lmul", "pipeline",
                             "classify", "serve", "roofline"])
    args = ap.parse_args()

    from repro.core import jaxcache
    jaxcache.enable()

    from benchmarks import (bow_svm_bench, classify_bench, erode_bench,
                            filter2d_bench, lmul_bench, pipeline_bench,
                            serve_bench)
    from benchmarks.common import RESULTS_PATH, flush_results, print_delta

    if args.only in (None, "lmul"):
        lmul_bench.run(quick=args.quick)
    if args.only in (None, "filter2d"):
        filter2d_bench.run(quick=args.quick)
    if args.only in (None, "erode"):
        erode_bench.run(quick=args.quick)
    if args.only in (None, "pipeline"):
        pipeline_bench.run(quick=args.quick, mode=args.mode)
        pipeline_bench.run_octave(quick=args.quick, mode=args.mode)
        pipeline_bench.run_warp(quick=args.quick, mode=args.mode)
        pipeline_bench.run_pyramid(quick=args.quick, mode=args.mode)
        pipeline_bench.run_small_kernel_routing(quick=args.quick)
    if args.only in (None, "bow"):
        bow_svm_bench.run(quick=args.quick)
    if args.only in (None, "classify"):
        classify_bench.run(quick=args.quick)
    if args.only in (None, "serve"):
        serve_bench.run(quick=args.quick)
    written = flush_results()
    if written:
        print(f"\nresults -> {written}")
        import json
        with open(RESULTS_PATH) as f:
            print_delta(json.load(f))
    if args.only in (None, "roofline"):
        art = os.path.join(os.path.dirname(__file__), "artifacts", "dryrun")
        if os.path.isdir(art) and os.listdir(art):
            from repro.roofline import analyze
            rows = analyze.load_all(art)
            for mesh in ("16x16", "2x16x16"):
                print(f"\n## Roofline — mesh {mesh} (from dry-run artifacts)\n")
                print(analyze.table(rows, mesh))
        else:
            print("\n(roofline: no dry-run artifacts; run python -m repro.launch.dryrun --all)")


if __name__ == "__main__":
    main()
