"""One process per chip: a launcher must stay off JAX's backend.

A chip belongs to one process.  A parent that has initialised a JAX
backend holds it, and the child it then starts — the bench leg that is
a whole Learner training, the supervised learner — fails or hangs
reaching for the same chip.  So ``bench.py``'s multi-process mains and
the ``supervise_learner`` guard must reach their first child with no
backend up; whole-Learner bench legs must not be pinned to the CPU
(the load generators and actor children are, by design); and a bench
whose child failed must exit non-zero after printing what it has.

All of it is observed in ONE fresh interpreter (this pytest process
initialised its backend long ago) with ``subprocess.run`` and the spawn
context's ``Process`` replaced by recorders: no child really starts.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

_OBSERVE = r"""
import json, runpy, subprocess, sys


def backend_up():
    jax = sys.modules.get("jax")
    return bool(jax) and jax._src.xla_bridge.backends_are_initialized()


class Failed:
    returncode, stdout, stderr = 1, "", "boom"


def fake_run(cmd, env=None, **kwargs):
    calls.append({"leg": " ".join(cmd[2:]), "backend_up": backend_up(),
                  "platform": (env or {}).get("JAX_PLATFORMS")})
    return Failed()


subprocess.run = fake_run
report = {}
for flag in ("--pipeline", "--serve", "--router", "--anakin"):
    calls, sys.argv = [], ["bench.py", flag, "1"]
    try:
        runpy.run_path("bench.py", run_name="__main__")
        code = 0
    except SystemExit as exc:
        code = exc.code
    report[flag] = {"exit": code, "calls": calls}

# main.py --train with supervise_learner: up to the guard's first spawn
from handyrl_tpu import connection, learner

spawns = []


class Child:
    exitcode = 0

    def __init__(self, target=None, args=()):
        spawns.append({"target": target.__name__,
                       "backend_up": backend_up()})

    def start(self):
        pass

    def join(self):
        pass


connection._mp.Process = Child
learner.train_main({"env_args": {"env": "TicTacToe"},
                    "train_args": {"supervise_learner": True}})
report["guard"] = spawns
print("\n" + json.dumps(report))
"""


@pytest.fixture(scope="module")
def observed():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c", _OBSERVE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


# which legs are whole Learner trainings (they take the chip) and which
# are CPU by design: load generators, and the virtual-device mesh legs
ON_CHIP = {
    "--pipeline": {"--pipeline-child off 3", "--pipeline-child on 3",
                   "--pipeline-child chaos 3"},
    "--serve": set(),
    "--router": set(),
    "--anakin": {"--anakin-host-child 3", "--anakin-child 3"},
}


@pytest.mark.parametrize("flag", sorted(ON_CHIP))
def test_bench_parent_stays_off_the_chip_and_fails_loudly(flag, observed):
    report, stdout = observed
    calls = report[flag]["calls"]
    assert calls, "the main never reached a child"
    # no backend was up when any child started
    assert not any(c["backend_up"] for c in calls), calls
    # whole-Learner legs inherit the platform; the rest are pinned
    for call in calls:
        expect = None if call["leg"] in ON_CHIP[flag] else "cpu"
        assert call["platform"] == expect, call
    assert {c["leg"] for c in calls} >= ON_CHIP[flag]
    # every child failed: the report still printed, the exit code says so
    assert report[flag]["exit"] == 1
    assert stdout.count('"error": "no complete rounds"') == len(ON_CHIP)


def test_learner_guard_parent_stays_off_the_chip(observed):
    report, _ = observed
    assert report["guard"] == [
        {"target": "_train_local", "backend_up": False}]
