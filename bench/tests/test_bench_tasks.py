"""The harness asks the cell's task for its frames' shape, its reference,
its control answers and its stencil chains, and assumes no task: a
stand-in erosion task on u8 C1 frames (`erode_task.py`) runs through
`harness.run` and `readings.read_seed` like the SIFT cells."""
import hashlib

import numpy as np
import pytest

import erode_task
import readings
import tinycells
from benchlib import frames, harness, spec, stencil_bytes

SEED = 2 ** 31 + 131

# sha256 of frames.pool(seed, 4, 48, 128) as it was drawn when every frame
# was u8 C3: the channel count may not change the frames of C3 cells
C3_DIGESTS = {
    7: "dacaba1356b8016e61dec9f7a3ddbe077ac29bcee74cb5915a9c9ffedd4254ac",
    2 ** 31 + 77:
        "02e8cd937ba318b5502a4563aa72ff7d6aae0ed4fc1d463e041db894f43aa625",
}


def _run(cell):
    return harness.run(cell, seed=SEED, seconds=1.0, trace=False,
                       platform="cpu", log=lambda m: None)


@pytest.mark.parametrize("seed", sorted(C3_DIGESTS))
def test_c3_pool_is_unchanged_and_c1_is_its_first_channel(seed):
    c3 = frames.pool(seed, 4, tinycells.H, tinycells.W)
    assert c3.shape == (4, tinycells.H, tinycells.W, 3)
    assert c3.dtype == np.uint8
    assert hashlib.sha256(c3.tobytes()).hexdigest() == C3_DIGESTS[seed]
    c1 = frames.pool(seed, 4, tinycells.H, tinycells.W, channels=1)
    assert c1.shape == (4, tinycells.H, tinycells.W, 1)
    np.testing.assert_array_equal(c1[..., 0], c3[..., 0])
    c4 = frames.pool(seed, 4, tinycells.H, tinycells.W, channels=4)
    np.testing.assert_array_equal(c4[..., :3], c3)


def test_frames_other_than_u8_are_refused():
    cfg = dict(erode_task.CONFIG,
               frame=dict(erode_task.CONFIG["frame"], dtype="uint16"))
    with pytest.raises(spec.SpecError):
        harness.Setup(erode_task.cell(cfg), SEED, platform="cpu")


def test_standin_task_sound_run_is_correct():
    r = _run(erode_task.cell())
    assert r.correct, r.checks
    assert r.checks["px_wrong"] == {"value": 0, "limit": 0}
    assert r.failed == 0 and r.attempted >= 2
    assert set(r.metrics) == {"images_per_s", "setup_s"}


def test_standin_task_shifted_frame_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    orig = erode_task.Engine._batch

    def shifted(self, x):               # one frame's output a pixel right
        out = orig(self, x)
        return out.at[0].set(jnp.roll(out[0], 1, axis=1))

    monkeypatch.setattr(erode_task.Engine, "_batch", shifted)
    r = _run(erode_task.cell())
    assert not r.correct, r.checks
    assert r.checks["px_wrong"]["value"] > 0


def test_standin_task_readings():
    row = readings.read_seed(erode_task.cell(), SEED, platform="cpu")
    assert row["frames"] == erode_task.TRAFFIC["pool"] and row["failed"] == 0
    assert row["program"] == {"px_wrong": 0}
    assert row["control"] == {"px_wrong": 0}    # erosion is exact in bf16
    assert readings.summary("erode_c1.b2", [row]) == {
        "workload": "erode_c1.b2", "seeds": 1, "lower": {"px_wrong": 0},
        "control": {"px_wrong": 0}}


def test_standin_stencil_bytes_by_hand():
    # one u8 plane in, one out, 48 x 128
    assert stencil_bytes.frame_bytes(erode_task.CONFIG, erode_task) \
        == 2 * 48 * 128 == 12288


@pytest.mark.parametrize("name", ["sift1080p.b16", "bow250svm1080p.b16"])
def test_sift_tasks_count_both_chains(name):
    c = spec.Cell(name, tinycells.ROOT)
    assert c.task.stencil_chains(c.config) == [
        (1080, 1920, 3, 3, "float32"), (1080, 1920, 1, 7, "float32")]
    assert stencil_bytes.frame_bytes(c.config, c.task) == 116121600


def test_summary_keeps_each_task_readings():
    rows = [{"seed": s, "frames": 4, "failed": 0, "kp_min": 3 + s,
             "kp_max": 9 + s, "program": {"label_gap": 0.01 * s},
             "control": {"label_gap": 0.3 - 0.1 * s},
             "tail_bf16": {"label_gap": 0.2 + s},
             "tail_half": {"label_gap": 0.1 + s}, "seconds": 1.0}
            for s in (1, 2)]
    got = readings.summary("bow250svm1080p.b16", rows)
    assert list(got) == ["workload", "seeds", "kp_max", "lower", "control",
                         "tail_bf16", "tail_half"]
    assert got["kp_max"] == 11
    assert got["lower"] == {"label_gap": 0.02}
    assert got["control"] == pytest.approx({"label_gap": 0.1})
    assert got["tail_half"] == {"label_gap": 1.1}
