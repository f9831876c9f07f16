"""The training cell at a tiny size on 4 virtual CPU devices: a sound
run is correct, each fault the cell can have, planted under the timed
path, makes `correct` false, and so does the control."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

CASE = Path(__file__).resolve().parent / "train_case.py"


def run_case(fault, tmp_path):
    p = subprocess.run([sys.executable, str(CASE), fault, str(tmp_path)],
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(tmp_path):
    out = run_case("none", tmp_path)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "altered_answer"])
def test_planted_fault_is_not_correct(fault, tmp_path):
    out = run_case(fault, tmp_path)
    assert out["correct"] is False, out["checks"]


def test_control_is_not_correct(tmp_path):
    out = run_case("control", tmp_path)
    limits = out["limits"]
    assert all(v <= limits[k] for k, v in out["program"].items()), out
    assert any(v > limits[k] for k, v in out["control"].items()), out


def test_epsilon_the_program_does_not_run_is_refused():
    """A configuration whose RMSNorm epsilon differs from the program's
    is refused at set-up, before anything compiles."""
    from bench import run as R
    from repro.configs import get_config

    driver = R.import_file(R.BENCH / "drivers" / "train.py", "train_eps")
    cfg = R.load_json(R.BENCH / "configs" / "smollm-360m.json")
    cfg["rms_norm_eps"] = 10 * driver.program_norm_eps(
        get_config(cfg["program_config"]))
    traffic = R.load_json(R.BENCH / "traffic" / "train-dp-8x1024.json")
    with pytest.raises(ValueError, match="rms_norm_eps"):
        driver.Cell(cfg, traffic, 2**31 + 7, devices=[])
