"""chip_smoke.py's control flow, rehearsed on the CPU at a tiny size.

The script's ``main()`` refuses to run without a TPU and has no bypass;
these call its own phase functions instead (TicTacToe, batch 4), so a
wrong path, argument or assertion is found here and not on the chip.
The mesh phase runs on the harness's virtual CPU devices: batch 4 makes
the learner pick dp=4 by itself, as it does on a four-chip host.
"""

import importlib.util
import json
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _tiny_args(smoke, **overrides):
    return smoke.flagship_args(
        env="TicTacToe", turn_based_training=True, forward_steps=4,
        batch_size=4, minimum_episodes=10, update_episodes=15,
        maximum_episodes=200, updates_per_epoch=20, lockstep_episodes=4,
        policy_target="TD", transfer_dtype="auto",
        # the CPU has no row in the peak table: mfu needs these
        perf={"peak_tflops": 1.0, "peak_hbm_gbs": 100.0}, **overrides)


def test_one_chip_phases_on_the_cpu(smoke, tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)      # train_phase moves the CWD
    args = _tiny_args(smoke, mesh={"dp": 1})
    learner, records = smoke.train_phase(args, str(tmp_path / "run"))
    assert learner.trainer.train_mesh is None
    smoke.check_training(learner, records, "cpu")
    smoke.eval_phase(args, games=4, processes=2)
    out = capsys.readouterr().out
    for line in ("fused step cold compile", "steps/s end to end",
                 "peak HBM", "episodes received", "env steps/s",
                 "win rate"):
        assert line in out, line


def test_four_chip_phases_on_virtual_devices(smoke, tmp_path,
                                             monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    args = _tiny_args(smoke)         # no mesh key: the learner picks dp
    learner, records = smoke.train_phase(args, str(tmp_path / "run"))
    smoke.check_training(learner, records, "cpu")
    batch = smoke.check_mesh(learner, records, 4)
    smoke.compare_sharded_step(learner, batch, 4)
    out = capsys.readouterr().out
    held = out.split("dp=4 vs single device, float32 at precision highest")
    assert "within tolerance: True" in held[1].split("\n")[0]
    assert "dp=4 vs single device, bfloat16:" in out


def test_main_refuses_without_a_tpu(tmp_path):
    """As the driver first runs it: in a sandbox, where it must fail —
    non-zero, ``"ok": false`` last, and nothing trained."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert not (REPO / "runs" / "chip_smoke" / "metrics.jsonl").exists()
