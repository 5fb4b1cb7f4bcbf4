"""The output check: the plain reference against itself, against the
configuration's stated precision and against the control."""

import os
import subprocess
import sys

import jax
import pytest
import yaml

from benchmarks.harness import check, corpus, weights
from benchmarks.harness.cells import BENCH_DIR, ROOT

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def geese():
    with open(f"{BENCH_DIR}/configs/geese32.yaml") as f:
        config = yaml.safe_load(f)
    train = dict(config["train_args"], batch_size=32, seed=5)
    train["lockstep_episodes"] = 4
    config = dict(config, train_args=train)
    episodes = corpus._play(config, 24, 11)

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models.wrapper import TPUModel

    env = make_env(config["env_args"])
    env.reset()
    model = TPUModel(env.net())
    shapes = weights.param_shapes(
        model.module, env.observation(env.players()[0]), None)
    initial = jax.device_get(weights.make_params(shapes, 5))
    follow = {lowp: check.reference_follow(
        config, train, episodes, 64, initial, lowp=lowp)
        for lowp in (None, "bf16", "fp8")}
    return initial, follow, config["check_limits"]


def test_the_control_comes_out_not_correct(geese):
    """The reference computed in fp8, the step below the stated
    bfloat16, put in the program's place: ``correct`` must be false,
    and it is the gradient's number that fails."""
    initial, follow, limits = geese
    numbers = check.training_numbers(
        check.as_captured(follow["fp8"]), follow[None], initial)
    correct, lines = check.verdict(numbers, limits)
    assert not correct, lines
    assert numbers["grad_gap"] > limits["grad_gap"]
    assert numbers["grad_diff"] > limits["grad_diff"]


def test_the_stated_precision_and_the_reference_itself_pass(geese):
    initial, follow, limits = geese
    for lowp in (None, "bf16"):
        numbers = check.training_numbers(
            check.as_captured(follow[lowp]), follow[None], initial)
        assert check.verdict(numbers, limits)[0], (lowp, numbers)
    same = check.training_numbers(
        check.as_captured(follow[None]), follow[None], initial)
    assert max(same.values()) < 1e-6
    assert set(same) <= set(limits)


def test_a_step_that_changes_nothing_fails_the_update_number(geese):
    initial, follow, limits = geese
    stuck = dict(check.as_captured(follow[None]),
                 params_after_third=initial)
    numbers = check.training_numbers(stuck, follow[None], initial)
    assert numbers["update_gap"] == pytest.approx(1.0)
    assert not check.verdict(numbers, limits)[0]


def test_a_part_of_the_batch_left_out_fails_the_loss_number(geese):
    initial, follow, limits = geese
    captured = check.as_captured(follow[None])
    captured["losses"] = [0.75 * x for x in captured["losses"]]
    numbers = check.training_numbers(captured, follow[None], initial)
    losses, _, _, scales = follow[None]
    assert numbers["loss_gap"] == pytest.approx(
        max(0.25 * abs(x) / s for x, s in zip(losses, scales)))
    assert all(s >= abs(x) for x, s in zip(losses, scales))
    assert not check.verdict(numbers, limits)[0]


def _rehearse(*argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), *argv],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def _result(proc):
    import json

    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_a_broken_timed_path_comes_out_not_correct():
    """The rest of a run, driven without the look for a chip, with the
    fused step returning its state unchanged underneath the harness."""
    result = _result(_rehearse("geese.fed", "noop_step"))
    assert result["correct"] is False
    assert result["device"]["platform"] == "cpu"
