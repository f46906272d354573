"""What the two SIFT tasks (`extract`, `classify`) share: the stencil
chains that one frame runs, and the keypoint counts of the readings."""
from __future__ import annotations

import numpy as np


def stencil_chains(cfg: dict) -> list:
    """The preprocess chain (C planes in, C out), then one SIFT octave
    (the gray plane in, one plane per scale out), at the configured plane
    dtype.  More than one octave is not counted yet."""
    if cfg["n_octaves"] != 1:
        raise NotImplementedError("stencil bytes of a multi-octave pyramid")
    f = cfg["frame"]
    h, w, c = f["height"], f["width"], f["channels"]
    dt = cfg["preprocess"]["plane_dtype"]
    return [(h, w, c, c, dt), (h, w, 1, len(cfg["octave_taps"]), dt)]


def keypoint_range(refs: dict) -> dict:
    """Fewest and most keypoints the reference finds in a frame."""
    kp = [int(np.asarray(r["valid"]).sum()) for r in refs.values()]
    return {"kp_min": min(kp), "kp_max": max(kp)}
