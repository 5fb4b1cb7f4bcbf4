"""One process per chip: a launcher must stay off JAX's backend.

A chip belongs to one process.  A parent that has initialised a JAX
backend holds it, and the child it then starts — the supervised
learner — fails or hangs reaching for the same chip.  So the
``supervise_learner`` guard must reach its first child with no backend
up.

It is observed in a fresh interpreter (this pytest process initialised
its backend long ago) with the spawn context's ``Process`` replaced by
a recorder: no child really starts.
"""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

_OBSERVE = r"""
import json, sys


def backend_up():
    jax = sys.modules.get("jax")
    return bool(jax) and jax._src.xla_bridge.backends_are_initialized()


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
print("\n" + json.dumps({"guard": spawns}))
"""


@pytest.fixture(scope="module")
def observed():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, "-c", _OBSERVE], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_learner_guard_parent_stays_off_the_chip(observed):
    assert observed["guard"] == [
        {"target": "_train_local", "backend_up": False}]
