"""Share of the HBM roofline the filter task's fused chain reaches (the
least bytes of its chains at the chip's HBM bandwidth, over their device
time): the reader of `stencil_roofline`, under the name the filter cell
is listed by."""
import os

from benchlib import spec

read = spec.load_module(
    os.path.join(os.path.dirname(__file__), "stencil_roofline.py"),
    "bench_metric_stencil_roofline").read
