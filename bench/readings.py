#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from; not part of a run.

    python3 bench/readings.py --workload sift1080p.b16 --seeds 1 2 3 ...

For each seed, in one process on the chip: the cell's set-up, then every
frame of its pool served once through the cell's own engine at the
cell's batch, and each compared number read against the float32 plain
reference: for what the program served (the lower reading) and for the
control, the reference itself computed in bfloat16 (the upper reading).
A classify cell adds two readings of its tail alone, on the float32
reference's descriptors: the tail in bfloat16, and the tail with half of
each frame's descriptors left out of its histogram.  One JSON line per
seed on standard output; a summary last.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_answers(refs: dict) -> list:
    """The reference computed in the control's precision, as answers."""
    import numpy as np
    ans = []
    for i, o in sorted(refs.items()):
        if "scores" in o:
            ans.append((i, {"label": int(np.argmax(o["scores"]))}))
        else:
            ans.append((i, {"desc": o["desc"], "valid": o["valid"]}))
    return ans


def tail_answers(refs: dict, head, *, dtype: str, keep_half: bool) -> list:
    """Labels from the reference's float32 descriptors through the
    reference tail alone, in `dtype`, or with the second half of each
    frame's keypoints left out of its histogram."""
    import jax.numpy as jnp
    import numpy as np
    from benchlib import reference
    ans = []
    for i, o in sorted(refs.items()):
        valid = np.asarray(o["valid"], bool)
        if keep_half:
            valid = valid & (np.cumsum(valid) <= valid.sum() // 2)
        s = reference.bow_scores(jnp.asarray(o["desc"]), jnp.asarray(valid),
                                 *head, jnp.dtype(dtype))
        ans.append((i, {"label": int(np.argmax(np.asarray(s, np.float32)))}))
    return ans


def misses(answers, refs: dict, atol: float) -> list:
    """Where a served descriptor has no reference counterpart within the
    tolerance: (pool frame, keypoint, its nearest reference distance),
    to tell an orientation bin crossing (a few hundredths) from another
    keypoint in the top-k (tenths)."""
    import numpy as np
    out, seen = [], set()
    for i, a in answers:
        if i in seen:
            continue
        seen.add(i)
        r = refs[i]
        got, want = a["desc"][a["valid"]], r["desc"][r["valid"]]
        near = np.array([np.abs(g[None] - want).max(axis=-1).min()
                         for g in got])
        for k in np.nonzero(near > atol)[0]:
            out.append([int(i), int(k), float(near[k])])
    return out


def read_seed(cell, seed: int) -> dict:
    from benchlib import harness
    st = harness.Setup(cell, seed)
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

    kp = [int(r["valid"].sum()) for r in refs.values()]
    row = {"seed": seed, "frames": len(answers), "failed": bad,
           "kp_min": min(kp), "kp_max": max(kp),
           "program": values(answers), "control": values(control_answers(low))}
    if head is not None:
        row["tail_bf16"] = values(tail_answers(refs, head, dtype="bfloat16",
                                               keep_half=False))
        row["tail_half"] = values(tail_answers(refs, head, dtype="float32",
                                               keep_half=True))
    else:
        row["misses"] = misses(answers, refs, cell.task.DESC_ATOL)
    return row


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
    names = rows[0]["program"].keys()
    summary = {"workload": a.workload, "seeds": len(rows),
               "kp_max": max(r["kp_max"] for r in rows),
               "lower": {n: max(r["program"][n] for r in rows) for n in names}}
    for side in ("control", "tail_bf16", "tail_half"):
        if side in rows[0]:
            summary[side] = {n: min(r[side][n] for r in rows) for n in names}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
