#!/usr/bin/env python3
"""Benchmark of CvEngine on a TPU, one cell per run.

    python3 bench/run.py --workload sift1080p.b16 --seed 7 --seconds 10 --trace 0

Runs from the root of a checkout.  The cell (`BENCHMARK.json`
`workloads`) names a configuration (`bench/configs/`), a traffic mix
(`bench/traffic/`) and its chips.  Set-up makes the frames and any
classifier from `--seed`, warms the cell's one batch shape and counts as
`setup_s`; then one client submits in a closed loop for `--seconds`.
With `--trace 0` the result holds the cell's end-to-end metrics; with
`--trace 1` the window runs under the profiler and the result holds the
per-layer metrics read from the trace (a metric whose reader finds
nothing is left out, and the run says so on standard error).  Either way
every served answer is then held to the plain reference, and the numbers
compared are printed beside their limits, last on standard error and
last in the result line.

The last line of standard output is one JSON object: correct, attempted,
failed, metrics, device[, breakdown], checks.  With no TPU, too few
chips, or no program in the checkout, the run exits nonzero and prints
no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from benchlib import harness, spec

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        harness.use_program(ROOT)
        cell = spec.Cell(a.workload, ROOT)
        res = harness.run(cell, seed=a.seed, seconds=a.seconds,
                          trace=bool(a.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"bench: no chip: {e}")
        return EXIT_NO_CHIP
    except harness.NoProgram as e:
        log(f"bench: {e}")
        return EXIT_NO_PROGRAM
    except Exception:                   # noqa: BLE001 — report, exit 1
        traceback.print_exc()
        return 1
    for n in res.notes:
        log(f"bench: {n}; left out of the result")
    for name, c in res.checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(res.line()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
