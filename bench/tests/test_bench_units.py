"""Byte counts, window statistics and the files the harness finds by name."""
import json
import os
import statistics

import pytest

import tinycells  # noqa: F401  (puts bench/ and src/ on the path)
from benchlib import spec, stats, stencil_bytes

BENCHMARK = spec.benchmark(tinycells.ROOT)


def test_stencil_bytes_by_hand():
    # preprocess: 3 f32 planes in + 3 out; octave: 1 in + 7 scales out
    plane = 1080 * 1920 * 4
    c = spec.Cell("sift1080p.b16", tinycells.ROOT)
    assert stencil_bytes.frame_bytes(c.config, c.task) == (6 + 8) * plane \
        == 116121600
    assert stencil_bytes.chain_bytes(2, 3, 1, 1, "bfloat16") == 24


def test_stencil_bytes_refuses_a_pyramid():
    c = spec.Cell("sift1080p.b16", tinycells.ROOT)
    with pytest.raises(NotImplementedError):
        stencil_bytes.frame_bytes(dict(c.config, n_octaves=4), c.task)


def test_p95_over_every_submit():
    lat = list(range(1, 101))               # 1..100
    assert stats.percentile(lat, 95) == pytest.approx(95.05)
    assert stats.percentile(lat, 50) == pytest.approx(statistics.median(lat))
    assert stats.percentile([7.0], 95) == 7.0


def test_images_per_s_is_the_whole_window():
    # three submits, a gap between the second and third still counts
    subs = [(0.0, 1.0, 16), (1.0, 2.0, 16), (3.0, 4.0, 16)]
    assert stats.images_per_s(subs) == pytest.approx(48 / 4.0)
    with pytest.raises(ValueError):
        stats.images_per_s([])


def test_spread_is_iqr_over_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


@pytest.mark.parametrize("w", [w["name"] for w in BENCHMARK["workloads"]])
def test_cells_find_their_files_by_name(w):
    c = spec.Cell(w, tinycells.ROOT)
    assert c.config["name"] == c.workload["config"]
    assert c.traffic["batch"] >= 1 and c.traffic["pool"] >= 2 * c.traffic["batch"]
    for fn in ("head", "engine", "submit", "answer", "numbers", "reference",
               "control_answer", "stencil_chains"):
        assert callable(getattr(c.task, fn))
    assert c.config["limits"], "no limit for correct"
    names = {m["name"] for m, _ in c.per_layer()}
    assert names, f"{w} reports no per-layer metric"
    for _, reader in c.per_layer():
        assert callable(reader.read)
    e2e = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.Cell("no.such.cell", tinycells.ROOT)


def test_benchmark_file_keeps_to_its_shape():
    top = {"command", "paths", "run_seconds", "configs", "workloads",
           "end_to_end", "per_layer"}
    assert set(BENCHMARK) == top
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        path = os.path.join(tinycells.BENCH, "metrics", m["name"] + ".py")
        assert os.path.isfile(path), path
    for c in BENCHMARK["configs"]:
        assert os.path.isfile(os.path.join(tinycells.ROOT, c["file"]))
    assert len(json.dumps(BENCHMARK)) < 64 * 1024
