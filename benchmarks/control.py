"""The control of the output check, read at a cell's own size.

    python3 benchmarks/control.py --workload <name> --seeds 1 2 3 [--lowp fp8]

For each seed: the same corpus, priming order and weights a run of the
cell would have; the plain reference follows the first three steps in
float32, and again in the control's precision (``fp8`` is the step below
the configurations' bfloat16); the control is then put in the program's
place and its numbers printed beside the limits.  A control that comes
out ``correct`` means a limit is too loose.  This builds no Learner and
measures no time; it runs on the chip so that the numbers are the
chip's arithmetic.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_numbers(cell, seed, lowp, capacity=4096):
    import jax

    from benchmarks.harness import check, corpus as corpus_mod
    from benchmarks.harness import priming, weights
    from handyrl_tpu import staging
    from handyrl_tpu.environment import make_env, prepare_env
    from handyrl_tpu.models.wrapper import TPUModel

    config = cell.config
    args = cell.program_args()
    train = args["train_args"]
    corpus = corpus_mod.load_corpus(cell.config_name, config)
    prepare_env(args["env_args"])
    env = make_env(args["env_args"])
    env.reset()
    model = TPUModel(env.net())
    shapes = weights.param_shapes(
        model.module, env.observation(env.players()[0]),
        model.init_hidden([1]))
    initial = jax.device_get(weights.config_params(shapes, seed, config))
    t_max = -(-int(config["horizon_steps"]) // staging._GROW_ROUND) \
        * staging._GROW_ROUND
    groups, rest = priming.prime_groups(
        staging, corpus, train["minimum_episodes"], t_max, seed)
    primed = [ep for group in groups for ep in group] + rest
    reference = check.reference_follow(
        config, train, primed, capacity, initial)
    control = check.reference_follow(
        config, train, primed, capacity, initial, lowp=lowp)
    return check.training_numbers(
        check.as_captured(control), reference, initial)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--lowp", default="fp8", choices=("fp8", "bf16"))
    opts = parser.parse_args(argv)

    from benchmarks.harness import check
    from benchmarks.harness.cells import Cell, load_manifest

    cell = Cell(load_manifest(), opts.workload)
    import jax

    print("device", jax.devices()[0].device_kind, flush=True)
    for seed in opts.seeds:
        numbers = control_numbers(cell, seed, opts.lowp)
        correct, _ = check.verdict(numbers, cell.config["check_limits"])
        print("control", json.dumps({
            "workload": cell.name, "seed": seed, "lowp": opts.lowp,
            "correct": correct, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
