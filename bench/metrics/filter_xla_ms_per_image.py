"""Device time outside the Pallas kernels per served frame (in the filter
task, the pad, crop and layout copies around the fused chain's launch):
the reader of `sift_xla_ms_per_image`, under the name the filter cell is
listed by."""
import os

from benchlib import spec

read = spec.load_module(
    os.path.join(os.path.dirname(__file__), "sift_xla_ms_per_image.py"),
    "bench_metric_sift_xla_ms_per_image").read
