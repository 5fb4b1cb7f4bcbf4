"""Drive one cell end to end at a tiny size on whatever backend JAX
finds: the rehearsal the tests make in a process of their own.

    python benchmarks/tests/rehearse.py <workload> [noop_step]

The chip check is skipped only here, through ``run.main(rehearsal=)``,
which the command line of run.py cannot reach.
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


if __name__ == "__main__":
    from benchmarks import run

    rehearsal = dict(TINY)
    rehearsal["train_args"] = dict(
        TINY["train_args"], batch_size=int(os.environ.get(
            "REHEARSAL_BATCH", "16")))
    if "noop_step" in sys.argv[2:]:
        rehearsal["wrap_step"] = noop_step
    code = run.main(["--workload", sys.argv[1], "--seed", str(2**31 + 77),
                     "--seconds", "4", "--trace", "0"], rehearsal=rehearsal)
    os._exit(code)
