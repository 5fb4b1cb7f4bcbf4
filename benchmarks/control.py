"""The control of the output check, read at a cell's own size.

    python3 benchmarks/control.py --workload <name> --seeds 1 2 3 [--lowp fp8]
    python3 benchmarks/control.py --workload <name> --seeds 1 2 3 --fault half_batch

For each seed: the same corpus, priming order and weights a run of the
cell would have; the plain reference follows the first three steps in
float32, and again in the control's precision (``fp8`` is the step below
the configurations' bfloat16); the control is then put in the program's
place and its numbers printed beside the limits.  A control that comes
out ``correct`` means a limit is too loose.  ``--fault half_batch``
reads a fault the same way: half of each batch's rows left out, the
mean taken over the rest.  This builds no Learner and
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


def inputs(cell, seed):
    """``(config, train, primed, initial)``: what a run of the cell at
    this seed hands its plain reference."""
    import jax

    from benchmarks.harness import corpus as corpus_mod
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
    return config, train, primed, initial


def control_numbers(cell, seed, lowp, capacity=4096):
    from benchmarks.harness import check

    config, train, primed, initial = inputs(cell, seed)
    reference = check.reference_follow(
        config, train, primed, capacity, initial)
    control = check.reference_follow(
        config, train, primed, capacity, initial, lowp=lowp)
    return check.training_numbers(
        check.as_captured(control), reference, initial)


def half_batch_numbers(cell, seed, capacity=4096):
    """Half of the batch left out, the mean taken over the rest: the
    reference follows the first half of each batch's rows, its loss
    and gradient scaled back to the whole batch's, and is put in the
    program's place."""
    import jax

    from benchmarks.harness import check

    config, train, primed, initial = inputs(cell, seed)
    training, net, batches = check.reference_batches(
        config, train, primed, capacity)
    reference = training.follow(net, initial, batches, train)
    rows = len(jax.tree.leaves(batches[0])[0])
    kept = max(1, rows // 2)
    if kept == rows:
        raise SystemExit(f"{cell.name}: a batch of one row has no half")
    losses, first, final, scales = training.follow(
        net, initial, [jax.tree.map(lambda a: a[:kept], b)
                       for b in batches], train)
    back = rows / kept
    faulted = ([back * x for x in losses],
               jax.tree.map(lambda g: back * g, first), final, scales)
    return check.training_numbers(
        check.as_captured(faulted), reference, initial)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--lowp", default="fp8", choices=("fp8", "bf16"))
    parser.add_argument("--fault", choices=("half_batch",),
                        help="read this fault in place of the control")
    opts = parser.parse_args(argv)

    from benchmarks.harness import check
    from benchmarks.harness.cells import Cell, load_manifest

    cell = Cell(load_manifest(), opts.workload)
    import jax

    print("device", jax.devices()[0].device_kind, flush=True)
    for seed in opts.seeds:
        if opts.fault:
            numbers, what = half_batch_numbers(cell, seed), opts.fault
        else:
            numbers = control_numbers(cell, seed, opts.lowp)
            what = opts.lowp
        correct, _ = check.verdict(numbers, cell.config["check_limits"])
        print("control", json.dumps({
            "workload": cell.name, "seed": seed, "lowp": what,
            "correct": correct, **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
