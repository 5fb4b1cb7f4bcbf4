"""Drive one cell end to end at a tiny size on whatever backend JAX
finds: the rehearsal the tests make in a process of their own.

    python benchmarks/tests/rehearse.py <workload> [noop_step] [traced]

The chip check is skipped only here, through ``run.main(rehearsal=)``,
which the command line of run.py cannot reach.  ``traced`` makes the
run a ``--trace 1`` one: the CPU's profile holds no TPU plane, so the
small trace recorded on a v5e (``data/small_trace.json``) is reduced in
its place, and each step of the order after the window prints
``order <name>`` as it starts.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TINY = {
    "traffic": {"warm_steps": 3, "warm_offers": 5, "rate_eps": 4},
    "train_args": {"minimum_episodes": 40, "update_episodes": 20,
                   "updates_per_epoch": 3, "device_replay_mb": 256,
                   "compute_dtype": "float32"},
    "corpus": {"episodes": 48},
}


def noop_step(real_step):
    """A fused step that hands back the state it was given: what a
    change that skipped the update would look like to the harness."""
    import jax
    import jax.numpy as jnp

    def step(params, opt_state, buffers, state):
        kept = jax.tree.map(jnp.copy, (params, opt_state))
        _, _, metrics, state = real_step(params, opt_state, buffers, state)
        return kept[0], kept[1], metrics, state

    return step


def traced(run):
    """Stand the recorded trace in for the CPU's, and mark the order of
    what ``run.main`` does after its window."""
    import functools
    import json

    from benchmarks.harness import roofline, trace
    from handyrl_tpu.learner import Trainer

    def recorded(_path):
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "data", "small_trace.json")) as f:
            return json.load(f)

    def marked(name, fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            print(f"order {name}", flush=True)
            return fn(*args, **kwargs)
        return call

    trace.load = recorded
    # the recorded trace is a v5e's: its step is held to that chip's peaks
    roofline.PEAKS["cpu"] = roofline.PEAKS["TPU v5 lite"]
    run._check_ring_rows = marked("ring_rows", run._check_ring_rows)
    run._release_device = marked("release", run._release_device)
    # the capture itself, not the cached answer the readers then read
    Trainer._capture_step_profile = marked(
        "step_profile", Trainer._capture_step_profile)


if __name__ == "__main__":
    from benchmarks import run

    rehearsal = dict(TINY)
    rehearsal["train_args"] = dict(
        TINY["train_args"], batch_size=int(os.environ.get(
            "REHEARSAL_BATCH", "16")))
    if "noop_step" in sys.argv[2:]:
        rehearsal["wrap_step"] = noop_step
    trace = "traced" in sys.argv[2:]
    if trace:
        traced(run)
    code = run.main(["--workload", sys.argv[1], "--seed", str(2**31 + 77),
                     "--seconds", "4", "--trace", str(int(trace))],
                    rehearsal=rehearsal)
    os._exit(code)
