"""`chip_smoke.py` rehearsed on the CPU: its phases at a tiny size in
interpret mode (frames above the octave chain's halo, so both stencil
kernels launch; a k=8 dictionary), the failure paths, and the exit code
where there is no TPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

import pytest

from repro.core import faultinject

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
HW = (64, 256)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def extracted(smoke):
    imgs, _ = smoke.frames(2, HW)
    return imgs, smoke.phase_extract(imgs)


def test_extract_phase_interpret(extracted):
    _, out = extracted
    assert len(out["responses"]) == 2
    assert out["pallas_calls"] == 2          # preprocess chain + octave chain
    assert out["valid_kp"] > 0
    assert out["desc_max_diff"] <= 2e-3


def test_classify_phase_interpret(smoke, extracted):
    imgs, ex = extracted
    train, labels = smoke.frames(4, HW, split="train")
    out = smoke.phase_classify(train, labels, imgs, ex["responses"],
                               dict_size=8)
    assert out["pallas_calls"] == 2
    assert len(out["labels"]) == 2


def test_injected_degradation_fails_phase(smoke):
    imgs, _ = smoke.frames(1, HW)
    with faultinject.inject("bucket_miss"):
        with pytest.raises(smoke.SmokeFailure, match="degradation"):
            smoke.phase_extract(imgs)
    faultinject.clear_degradation_log()


def test_device_phase_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="not 'tpu'"):
        smoke.phase_device()


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out


def test_lone_script_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
