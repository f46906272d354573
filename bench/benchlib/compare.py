"""The numbers that decide `correct`: each served answer against the
plain reference's answer for the same frame."""
from __future__ import annotations

import numpy as np


def unmatched_share(desc, valid, ref_desc, ref_valid, atol: float,
                    block: int = 64) -> float:
    """Share of one frame's keypoints, on both sides, that have no
    counterpart on the other side within `atol` in every descriptor
    element.  Order does not count (top-k ties may order keypoints
    differently); a missing or extra keypoint counts as unmatched.
    Served rows are compared `block` at a time."""
    got = np.asarray(desc)[np.asarray(valid, bool)]
    want = np.asarray(ref_desc)[np.asarray(ref_valid, bool)]
    total = len(got) + len(want)
    if total == 0:
        return 0.0
    if len(got) == 0 or len(want) == 0:
        return 1.0
    got_hit = np.zeros(len(got), bool)
    want_hit = np.zeros(len(want), bool)
    for i in range(0, len(got), block):
        d = np.abs(got[i:i + block, None, :] - want[None]).max(axis=-1) <= atol
        got_hit[i:i + block] = d.any(axis=1)
        want_hit |= d.any(axis=0)
    return float((~got_hit).sum() + (~want_hit).sum()) / total


def label_gap(scores, label) -> float:
    """How far the served label's reference score lies below the
    reference's best (0 where the served label is the reference's)."""
    s = np.asarray(scores, np.float64)
    if label is None or not 0 <= int(label) < len(s):
        return float("inf")
    return float(s.max() - s[int(label)])
