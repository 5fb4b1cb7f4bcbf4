"""Chip smoke: the flagship learner-actor path once, through train_main.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the dp=4 path only, on a 4-chip host

One chip: writes the flagship config (HungryGeese / GeeseNet 32f x 12,
simultaneous UPGO/TD, batch 256 x 8 steps, bf16 compute, uint8 wire)
into ``runs/chip_smoke/`` and calls ``handyrl_tpu.learner.train_main``
on it — the function ``main.py --train`` dispatches to: HBM replay ring
+ fused draw/gather/update step + inference service in this process,
CPU actors as the program's own spawned children.  Then it holds the
run to its own records (metrics.jsonl, the learner's guards and cost
model, where the arrays live) and evaluates the checkpoint through
``evaluation.eval_main`` (``main.py --eval``).

Every line but the last is an observation for the next reader, not a
claim.  The LAST stdout line is the result and nothing else:
``{"ok": true, "device": {"platform", "kind", "count"}}``.

The script refuses to run without a TPU (non-zero, ``"ok": false``) and
never sets JAX_PLATFORMS: a smoke that carried on on the CPU would
prove nothing.  One process owns the chip, so everything runs here;
the children (actors, eval matches) pin the CPU themselves.  Spawn
re-imports this file in every child, hence no JAX at import and
nothing outside the functions but the ``__main__`` guard.
"""

import argparse
import json
import math
import os
import random
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_DIR = os.path.join(HERE, "runs", "chip_smoke")   # fixed: see .gitignore
SEED = 22


def flagship_args(env="HungryGeese", **train_overrides):
    """runs/hungry_geese/config.yaml at batch 256, cut to two epochs
    (the overrides are for the CPU rehearsal in tests/)."""
    train_args = {
        "turn_based_training": False,
        "observation": False,
        "gamma": 0.8,
        "forward_steps": 8,
        "burn_in_steps": 0,
        "compress_steps": 4,
        "entropy_regularization": 0.1,
        "entropy_regularization_decay": 0.1,
        # epoch boundaries ride INTAKE, and episodes keep arriving
        # while the fused step compiles: windows this wide keep the
        # second epoch from ticking before the trainer has had time
        # for more than its one obligatory step
        "minimum_episodes": 256,
        "update_episodes": 2048,
        "maximum_episodes": 20000,
        "batch_size": 256,
        "epochs": 2,
        "updates_per_epoch": 200,
        "num_batchers": 2,
        "eval_rate": 0.05,
        "worker": {"num_parallel": 2},
        "lockstep_episodes": 32,
        "lambda": 0.7,
        "policy_target": "UPGO",
        "value_target": "TD",
        "eval": {"opponent": ["random"]},
        "seed": SEED,
        "restart_epoch": 0,
        "compute_dtype": "bfloat16",
        "transfer_dtype": "uint8",
        "metrics_path": "metrics.jsonl",
        "max_update_compiles": 1,
        "numerics_guard": True,
        "sharding_contract_guard": True,
    }
    train_args.update(train_overrides)
    return {
        "env_args": {"env": env},
        "train_args": train_args,
        "worker_args": {"server_address": "", "num_parallel": 2},
    }


def train_phase(args, run_dir):
    """Write the config, call train_main on it; (learner, records)."""
    import yaml

    from handyrl_tpu.learner import train_main

    shutil.rmtree(run_dir, ignore_errors=True)   # a fresh run, not a resume
    os.makedirs(run_dir)
    os.chdir(run_dir)   # models/ and metrics_path resolve against the CWD
    with open("config.yaml", "w") as f:
        yaml.safe_dump(args, f)
    with open("config.yaml") as f:
        args = yaml.safe_load(f)    # exactly what main.py would read
    t0 = time.monotonic()
    learner = train_main(args)      # raises if the trainer thread died
    wall = time.monotonic() - t0
    with open(args["train_args"]["metrics_path"]) as f:
        records = [json.loads(line) for line in f if line.strip()]
    print(f"train_main returned after {wall:.1f}s, "
          f"{len(records)} epoch records")
    return learner, records


def _number(x):
    return isinstance(x, (int, float)) and math.isfinite(x)


def _on_platform(tree, platform):
    import jax

    leaves = jax.tree.leaves(tree)
    return bool(leaves) and all(
        d.platform == platform for leaf in leaves for d in leaf.devices())


def check_training(learner, records, platform):
    """Hold the finished run to its own records; prints observations."""
    import jax

    trainer = learner.trainer
    replay = trainer.device_replay
    epochs = learner.args["epochs"]
    assert trainer.failure is None, trainer.failure
    assert len(records) == epochs >= 2, [r["epoch"] for r in records]
    steps = [r["steps"] for r in records]
    assert steps[0] > 0 and all(
        b > a for a, b in zip(steps, steps[1:])), steps
    for r in records:
        for key in ("p", "v", "ent", "total"):
            assert _number(r[key]), (r["epoch"], key, r[key])
        assert r["resharding_copies"] == 0, r
        assert r["nonfinite_steps"] == 0, r
        assert r["numerics_contract_breaks"] == 0, r
        assert _number(r["mfu"]) and r["mfu"] > 0, r
        assert _number(r["achieved_tflops"]), r
    # the step ran through the HBM ring, not the host-batcher feed
    assert replay is not None and trainer.batcher is None
    assert trainer._step_label == "replay_step"
    # ONE compile of the fused step, plus one per ring growth (a longer
    # episode than any before re-lays the ring: designed, and budgeted
    # the same way by max_update_compiles in the run itself)
    retraces = records[-1]["retrace_count"]
    assert 1 <= retraces <= 1 + replay.growths, (retraces, replay.growths)
    assert trainer.costmodel.harvest_failures == 0
    assert _on_platform(trainer.params, platform)
    assert _on_platform(trainer.opt_state, platform)
    assert _on_platform(replay.buffers, platform)
    assert os.path.exists(f"models/{epochs}.ckpt")

    first, last = records[0], records[-1]
    steps_last = steps[-1] - steps[-2]
    # epoch 0's step section holds the cold compile (the cost harvest's
    # and the call's own); what steady steps cost is read off the last
    per_step = last["profile_update_sec"] / max(steps_last, 1)
    compile_sec = first["profile_update_sec"] - steps[0] * per_step
    mean_len = float(replay.ep_len[:replay.size].mean())
    ring_mb = sum(leaf.nbytes for leaf in jax.tree.leaves(
        replay.buffers)) / 2**20
    stats = jax.devices()[0].memory_stats() or {}
    print(f"fused step cold compile (harvest + call, epoch 0): "
          f"{compile_sec:.1f}s")
    print(f"last epoch: {steps_last} steps in {last['epoch_wall_sec']}s "
          f"wall = {steps_last / last['epoch_wall_sec']:.2f} steps/s end "
          f"to end (intake-paced); {last['profile_update_sec']}s in step "
          f"dispatch, {last.get('profile_ingest_sec')}s in ring ingest; a "
          f"step in flight for {last['device_step_sec']}s, the device "
          f"starved for {last['starved_sec']}s, median run-ahead "
          f"{last['run_ahead_p50']} steps")
    print(f"steps by epoch: {steps}; retrace_count {retraces}, ring "
          f"growths {replay.growths} (t_max {replay.t_max}, capacity "
          f"{replay.capacity}, {ring_mb:.0f} MiB of arrays)")
    print(f"mfu {last['mfu']} achieved_tflops {last['achieved_tflops']} "
          f"roofline {last['roofline_verdict']} (cost model, over the "
          f"seconds a step was in flight)")
    print(f"peak HBM: {stats.get('peak_bytes_in_use')} bytes of "
          f"{stats.get('bytes_limit')}")
    print(f"episodes received {learner.episodes_received}, into the ring "
          f"{replay.episodes_seen}, shed while the trainer was busy "
          f"{replay.dropped}")
    print(f"actors: ~{mean_len * learner.episodes_received / last['time_sec']:.0f}"
          f" env steps/s ({mean_len:.1f} steps per ring episode x "
          f"episodes received / {last['time_sec']}s of run)")
    print(f"losses last epoch: p {last['p']:.4f} v {last['v']:.4f} "
          f"ent {last['ent']:.4f} total {last['total']:.4f}")


def eval_phase(args, games=8, processes=2):
    """The checkpoint through eval_main, as ``main.py --eval`` would:
    in THIS process (it holds the chip), match children on the CPU."""
    from handyrl_tpu.evaluation import eval_main, wp_func

    epochs = args["train_args"]["epochs"]
    table = eval_main(args, [f"models/{epochs}.ckpt", str(games),
                             str(processes)])
    played = sum(table.overall[0].values())
    rate = wp_func(table.overall[0])
    assert played == games, (played, games)
    assert 0.0 <= rate <= 1.0
    print(f"eval: models/{epochs}.ckpt vs random over {played} games: "
          f"win rate {rate:.3f}")


def check_mesh(learner, records, chips):
    """The mesh the learner picked by itself: dp over every chip, batch
    rows split across them, nothing whole on device 0 but what the
    sharding rules replicate.  Returns one host batch for the step
    comparison."""
    import jax

    trainer = learner.trainer
    mesh = trainer.train_mesh
    assert mesh is not None and dict(mesh.shape)["dp"] == chips, mesh
    assert len(set(mesh.devices.flat)) == chips
    assert all(r["resharding_copies"] == 0 for r in records)
    # replicated BY RULE (no tp, no fsdp; the ring rides replicated so
    # every chip gathers its own rows): present on every chip
    for tree in (trainer.params, trainer.opt_state,
                 trainer.device_replay.buffers):
        for leaf in jax.tree.leaves(tree):
            if not leaf.committed:
                continue   # the annealed lr scalar: placed by the next step
            assert len(leaf.sharding.device_set) == chips, leaf.sharding
            assert leaf.sharding.is_fully_replicated, leaf.sharding
    # the batch is what dp shards: one seeded draw, rows on four chips
    random.seed(SEED)
    rows = learner.args["batch_size"]
    batch = trainer.device_replay.sample(rows)
    for leaf in jax.tree.leaves(batch):
        shards = leaf.addressable_shards
        assert len({s.device for s in shards}) == chips, leaf.sharding
        assert all(s.data.shape[0] == rows // chips for s in shards)
    print(f"mesh: dp={chips} over {sorted(d.id for d in mesh.devices.flat)}"
          f"; batch rows {rows} -> {rows // chips} per chip")
    return jax.device_get(batch)


def compare_sharded_step(learner, batch, chips):
    """One seeded batch through the dp step and the single-device step:
    losses and updated params must agree to tests/test_parallel.py's
    tolerance.  Held in float32 at matmul precision ``highest``: that
    tolerance is for float32 MATH, and a TPU's default float32 conv
    rounds its operands to bf16 — the loss still matches to the bit,
    but gradients that nearly cancel then change sign with the
    summation order, and Adam's first step turns each such sign into a
    full +-lr (seen on four chips: worst difference exactly 2 lr).
    The production dtype, bf16, is printed beside it, not held."""
    import contextlib

    import jax
    import numpy as np

    from handyrl_tpu.ops.update import (
        DEFAULT_LR, make_optimizer, make_update_step)
    from handyrl_tpu.parallel import (
        MeshSpec, make_mesh, make_sharded_update_step)

    trainer = learner.trainer
    mesh = make_mesh(MeshSpec(dp=chips), devices=jax.devices()[:chips])
    lr = DEFAULT_LR * trainer.args["batch_size"] * \
        trainer.args["forward_steps"]
    batch = dict(batch, observation=jax.tree.map(
        lambda a: np.asarray(a, np.float32), batch["observation"]))

    def one_step(build, dtype):
        optimizer = make_optimizer(lr)
        params = jax.tree.map(jax.numpy.array, learner.model.params)
        step = build(optimizer, params, dtype)
        params, _, metrics = step(params, optimizer.init(params), batch)
        return jax.tree.leaves(jax.device_get(params)), \
            float(metrics["total"])

    def single(optimizer, params, dtype):
        return make_update_step(
            trainer.model, trainer.loss_cfg, optimizer, dtype)

    def sharded(optimizer, params, dtype):
        return make_sharded_update_step(
            trainer.model, trainer.loss_cfg, optimizer, mesh, params,
            compute_dtype=dtype)

    for dtype, precision in (("float32", "highest"), ("bfloat16", None)):
        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            ref_params, ref_total = one_step(single, dtype)
            dp_params, dp_total = one_step(sharded, dtype)
        assert math.isfinite(ref_total) and math.isfinite(dp_total)
        apart = sum(int((~np.isclose(a, b, rtol=2e-4, atol=2e-5)).sum())
                    for a, b in zip(ref_params, dp_params))
        worst = max(float(np.max(np.abs(a - b)))
                    for a, b in zip(ref_params, dp_params))
        agree = apart == 0 and \
            abs(dp_total - ref_total) <= 1e-4 * abs(ref_total)
        print(f"dp={chips} vs single device, {dtype}"
              f"{' at precision ' + precision if precision else ''}: "
              f"total loss {dp_total:.6f} vs {ref_total:.6f}; "
              f"{apart} of {sum(a.size for a in ref_params)} params "
              f"outside tolerance, worst difference {worst:.3g} "
              f"(lr {lr:.3g}); within tolerance: {agree}")
        if precision:
            assert agree, (dp_total, ref_total, apart, worst)


def main(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4))
    chips = parser.parse_args(argv).chips

    from handyrl_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != chips:
        print(json.dumps({"ok": False, "device": device, "error":
                          f"needs {chips} TPU chip(s); nothing trained"}))
        return 1
    print(f"compile cache: {cache_dir}")
    try:
        args = flagship_args()
        learner, records = train_phase(args, RUN_DIR)
        check_training(learner, records, "tpu")
        if chips == 1:
            eval_phase(args)
        else:
            batch = check_mesh(learner, records, chips)
            compare_sharded_step(learner, batch, chips)
    except Exception as exc:
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps({"ok": False, "device": device,
                          "error": repr(exc)}))
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
