#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from; not part of a run.

    python3 bench/readings.py --workload sift1080p.b16 --seeds 1 2 3 ...

For each seed, in one process on the chip: the cell's set-up, then every
frame of its pool served once through the cell's own engine at the
cell's batch, and each compared number read against the task's float32
plain reference: for what the program served (the lower reading) and for
the control, the reference itself computed in bfloat16 (the upper
reading).  The task may add readings of its own (`readings`; the
classify task reads its tail alone in bfloat16 and with half of each
frame's descriptors left out).  One JSON line per seed on standard
output; a summary last.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_answers(task, refs: dict) -> list:
    """The reference computed in the control's precision, as answers."""
    return [(i, task.control_answer(o)) for i, o in sorted(refs.items())]


def read_seed(cell, seed: int, *, platform: str = "tpu") -> dict:
    from benchlib import harness
    st = harness.Setup(cell, seed, platform=platform)
    tr = cell.traffic
    answers, bad = [], 0
    for k in range(-(-tr["pool"] // tr["batch"])):
        idx = st.schedule[k]
        res = st.submit(idx)
        bad += harness.served_ok(res, cell.task.PLAN)
        answers.extend((i, cell.task.answer(r)) for i, r in zip(idx, res))
    pool, head = st.pool, st.head
    del st
    served = sorted({i for i, _ in answers})
    refs = harness.references(cell, pool, head, served)
    low = harness.references(cell, pool, head, served, dtype="bfloat16")

    def values(ans):
        return {k: v["value"] for k, v in
                harness.judge(cell, ans, refs).items()}

    row = {"seed": seed, "frames": len(answers), "failed": bad,
           "program": values(answers),
           "control": values(control_answers(cell.task, low))}
    if hasattr(cell.task, "readings"):
        row.update(cell.task.readings(cell.config, answers, refs, head))
    return row


def summary(workload: str, rows: list) -> dict:
    """Over the seeds: the largest program reading (the lower end of a
    limit), the smallest control and task stand-in readings (the upper
    end), and the largest of each `*_max` reading."""
    names = rows[0]["program"].keys()
    out = {"workload": workload, "seeds": len(rows)}
    for k in rows[0]:
        if k.endswith("_max"):
            out[k] = max(r[k] for r in rows)
    out["lower"] = {n: max(r["program"][n] for r in rows) for n in names}
    for k, v in rows[0].items():
        if isinstance(v, dict) and k != "program":
            out[k] = {n: min(r[k][n] for r in rows) for n in names}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    from benchlib import harness, spec
    harness.use_program(ROOT)
    cell = spec.Cell(a.workload, ROOT)
    rows = []
    for seed in a.seeds:
        t = time.perf_counter()
        row = read_seed(cell, seed)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps(summary(a.workload, rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
