"""Device time of the filter task's fused chain (Pallas ops named
`_chain_planes`) per served frame: the reader of `stencil_ms_per_image`,
under the name the filter cell is listed by."""
import os

from benchlib import spec

read = spec.load_module(
    os.path.join(os.path.dirname(__file__), "stencil_ms_per_image.py"),
    "bench_metric_stencil_ms_per_image").read
