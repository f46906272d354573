"""The filter task (`filter4k_u8c1.b16`) on the CPU at 48x128: a sound
run reads `correct`; the control and each fault planted in the timed
path do not."""
import os

import pytest

import tinycells
from benchlib import harness, spec, stencil_bytes

NAME = "filter4k_u8c1.b16"
SEED = 2 ** 31 + 97


def _run(cell):
    return harness.run(cell, seed=SEED, seconds=1.0, trace=False,
                       platform="cpu", log=lambda m: None)


def _plant(monkeypatch, fault):
    """Break CvEngine's timed path underneath the harness."""
    import jax.numpy as jnp
    from repro.kernels import stencil
    from repro.serve import cv_engine
    orig = cv_engine.CvEngine._batch_fn
    last = {}

    def broken(self, x, rung):
        if fault == "no_erode":         # the chain's erode left out
            return {"image": stencil.fused_chain(x, self.chain[:1],
                                                 mode=rung)}
        out = orig(self, x, rung)
        v = out["image"]
        if fault == "shifted":          # one frame a pixel to the right
            v = v.at[0].set(jnp.roll(v[0], 1, axis=1))
        elif fault == "stale":          # the previous submit's images
            prev, last["v"] = last.get("v"), v
            v = prev if prev is not None else v
        return {"image": v}

    monkeypatch.setattr(cv_engine.CvEngine, "_batch_fn", broken)


def test_sound_run_is_correct():
    r = _run(tinycells.cell(NAME))
    assert r.correct, r.checks
    assert r.failed == 0 and r.attempted >= 2
    assert r.checks["px_diff_max"]["value"] <= 1
    assert set(r.metrics) == {"images_per_s", "setup_s"}


def test_control_is_not_correct():
    import readings
    cell = tinycells.cell(NAME)
    st = harness.Setup(cell, SEED, platform="cpu")
    served = list(range(cell.traffic["pool"]))
    refs = harness.references(cell, st.pool, st.head, served)
    low = harness.references(cell, st.pool, st.head, served,
                             dtype="bfloat16")
    checks = harness.judge(cell, readings.control_answers(cell.task, low),
                           refs)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("fault", ["shifted", "no_erode", "stale"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _plant(monkeypatch, fault)
    r = _run(tinycells.cell(NAME, batch=4, pool=8))
    assert not r.correct, (fault, r.checks)


def test_readings():
    import readings
    row = readings.read_seed(tinycells.cell(NAME), SEED, platform="cpu")
    assert row["frames"] == 4 and row["failed"] == 0
    assert row["program"]["px_diff_max"] <= 1
    assert 0 <= row["px_off_by_one_max"] <= row["program"]["px_off_share"]
    assert row["no_erode"]["px_diff_max"] > 1
    got = readings.summary(NAME, [row])
    assert list(got) == ["workload", "seeds", "px_off_by_one_max", "lower",
                         "control", "no_erode"]


def test_stencil_bytes_by_hand():
    # one u8 plane in, one out, 3840 x 2160
    c = spec.Cell(NAME, tinycells.ROOT)
    assert c.task.stencil_chains(c.config) == [(2160, 3840, 1, 1, "uint8")]
    assert stencil_bytes.frame_bytes(c.config, c.task) \
        == 2 * 3840 * 2160 == 16588800


def test_filter_readers_are_the_accepted_readers():
    """`filter_ms_per_image`, `filter_roofline` and
    `filter_xla_ms_per_image` are `stencil_ms_per_image`,
    `stencil_roofline` and `sift_xla_ms_per_image` under the filter
    cell's names: on one trace each pair reads the same number."""
    from benchlib import harness, trace
    kernel = ('%_chain_planes.1 = u8[2] custom-call(u8[2] %x), '
              'custom_call_target="tpu_custom_call"')
    tr = trace.from_events(
        {0: [trace.Event(10e3, 30e3, kernel),
             trace.Event(40e3, 45e3, "%fusion = u8[2]")]},
        [trace.Event(0, 100e3, trace.WINDOW_SPAN)])
    c = spec.Cell(NAME, tinycells.ROOT)
    ctx = harness.Context(config=c.config, submits=[], images=16,
                          devices=[0], device_kind="TPU v5 lite",
                          task=c.task, trace=tr)
    ours = {m["name"]: r.read(ctx) for m, r in c.per_layer()}
    assert set(ours) == {"filter_ms_per_image", "filter_roofline",
                         "filter_xla_ms_per_image"}
    load = {m["name"]: m for m in c.spec["per_layer"]}
    for name, same in (("filter_ms_per_image", "stencil_ms_per_image"),
                       ("filter_roofline", "stencil_roofline"),
                       ("filter_xla_ms_per_image", "sift_xla_ms_per_image")):
        assert same in load
        theirs = spec.load_module(
            os.path.join(spec.BENCH, "metrics", same + ".py"),
            "check_" + same).read(ctx)
        assert ours[name] == pytest.approx(theirs) and theirs > 0
    assert ours["filter_ms_per_image"] == pytest.approx(20e-3 / 16)
