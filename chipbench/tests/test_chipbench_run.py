"""A run of the harness on the CPU at a tiny size, with the look for a
chip skipped: the reference agrees with the train step, a sound run is
correct, and each fault planted under the timed path, and the control in
the program's place, makes it incorrect.  The command itself refuses a
CPU."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = ROOT / "chipbench" / "tests" / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import calibrate, checks, faults, harness, spec  # noqa: E402

SEED = 2 ** 31 + 12345


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_command_refuses_a_cpu_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "smollm135m-train-1chip", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=ROOT, env=_cpu_env(), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_in_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    env = _cpu_env()
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "resnet18cifar-train-b512-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["tiny-lm-1", "tiny-cnn-1"])
def test_reference_agrees_with_the_train_step(name):
    """Loss, first gradient and the change after the checked steps of
    ``build_train_step`` against the plain reference, within the cell's
    limits, which the control put in the program's place exceeds."""
    import jax
    cell = spec.load_cell(name, DATA)
    rows = []
    out = calibrate.readings(cell, jax.devices()[:1], [SEED], {SEED}, [],
                             [], emit=rows.append)
    (sound,), (control,) = out["program"], out["control"]
    ok, compared = checks.judge(sound, cell.limits)
    assert ok, compared
    ok, compared = checks.judge(control, cell.limits)
    assert not ok, compared
    assert [(r["kind"], r["correct"]) for r in rows] == [
        ("program", True), ("control", False)]


def test_a_cell_added_as_files_runs_and_reports(tmp_path):
    import jax
    base = tmp_path / "bench"
    shutil.copytree(DATA, base)
    w = json.loads((base / "workloads" / "tiny-lm-1.json").read_text())
    w["name"] = "tiny-lm-added"
    (base / "workloads" / "tiny-lm-added.json").write_text(json.dumps(w))
    cell = spec.load_cell("tiny-lm-added", base)
    res = harness.run(cell, SEED, 0.3, False, jax.devices(),
                      time.perf_counter(), log=lambda *a: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] == len(res["run"].step_s) > 0
    assert res["failed"] == 0
    run = res["run"]
    for name in ("tokens_per_s", "step_ms_p90", "setup_s"):
        assert spec.load_reader(name).read(run) > 0
    assert spec.load_reader("images_per_s").read(run) is None
    assert spec.load_reader("device.idle_pct.lm").read(run) is None


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_planted_fault_makes_the_run_incorrect(fault):
    import jax
    cell = spec.load_cell("tiny-lm-1", DATA)
    with faults.planted(fault):
        res = harness.run(cell, SEED, 0.2, False, jax.devices(),
                          time.perf_counter(), log=lambda *a: None)
    assert not res["correct"], res["checks"]
