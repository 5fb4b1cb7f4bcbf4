"""Benchmark: learner + actor + pipeline throughput vs the measured
reference.

Prints ONE JSON line:
  {"metric", "value", "unit", "vs_baseline", ...extras}

Headline: the PRODUCTION learner path — scalar-fed device-replay fused
step (draw + ring gather + update in one jit) on GeeseNet at batch 256
with bf16 compute — as the MEDIAN of interleaved trials: the solo /
device-replay / e2e sections run round-robin in one process N_TRIALS
times, so cross-path ratios are computed pairwise within rounds and no
number rests on a single pass (host timings swing between
processes).  ``vs_baseline`` is a REAL ratio against the
reference implementation's own update loop measured on this host at
the SAME batch geometry by scripts/measure_reference_baseline.py
(BASELINE_MEASURED.json — the reference trains one seat per
simultaneous-game episode, so the true flagship batch is
(256, 8, 1, 7, 11, 17)).

Extras:
  * measured (blocked) per-step device time + MFU from it — FLOPs are
    derived from the actual batch geometry and kernel shapes, not
    assumed constants;
  * end-to-end pipeline steps/s: batcher processes -> device prefetch
    (compact wire formats) -> update step, i.e. production training
    minus the actor plane, with the batch_wait/update split;
  * actor env-frames/sec from a CPU subprocess running the production
    RolloutPool (lockstep batched inference), plus the sequential
    number and a TicTacToe ratio against the measured reference actor;
  * episode-intake rate of the full WorkerCluster gather tree at 4, 16
    and 32 actor processes (scaling table).
"""

import json
import os
import subprocess
import sys
import time

BATCH = 256
SEED_EPS = 32          # distinct self-play episodes behind the batch
R1_GEOMETRY_BATCH = 64

# per-device-kind peaks live in ONE place now — the runtime cost model
# (telemetry.costmodel.DEVICE_PEAKS); bench's achieved-TFLOPs/MFU
# estimate rides the same reduction, so the offline numbers and the
# runtime metric can never disagree.  Unknown kinds -> mfu omitted.
from handyrl_tpu.telemetry.costmodel import mfu_extras  # noqa: E402


def _tile(batch, reps):
    import jax
    import numpy as np

    return jax.tree.map(
        lambda v: np.tile(v, (reps,) + (1,) * (v.ndim - 1)), batch)


def model_flops_per_sample(params, board_cells):
    """Analytic forward FLOPs per sample from the kernels:
    2 * spatial * kh * kw * cin * cout per conv, 2 * din * dout dense."""
    import jax

    total = 0.0
    for leaf in jax.tree.leaves(params):
        shape = getattr(leaf, "shape", ())
        if len(shape) == 4:  # NHWC conv kernel (kh, kw, cin, cout)
            kh, kw, cin, cout = shape
            total += 2.0 * board_cells * kh * kw * cin * cout
        elif len(shape) == 2:  # dense (din, dout)
            total += 2.0 * shape[0] * shape[1]
    return total


def batch_geometry(batch):
    """(samples per step, board cells) read off the actual batch —
    the forward flattens (B, T, P_in) into its batch dimension."""
    import jax

    obs = jax.tree.leaves(batch["observation"])[0]
    b, t, p_in = obs.shape[:3]
    cells = 1
    for d in obs.shape[3:-1]:
        cells *= d
    return b * t * p_in, cells


def _encode(batch, cfg):
    """Re-encode a float32 seed batch into the configured wire format."""
    from handyrl_tpu.batch import _encode_obs

    out = dict(batch)
    out["observation"] = _encode_obs(
        batch["observation"], cfg.get("transfer_dtype"))
    return out


def setup_learner(seed, batch_size, compute_dtype, iters=30,
                  host_iters=5, n_variants=4, timed_iters=10):
    """Build the update step + device-resident batch variants once.

    Returns (trial, host_sps, step_ms): ``trial()`` times ``iters``
    pipelined resident-batch steps and may be called repeatedly —
    interleaved with other sections, so cross-path ratios come from
    the same process window.  ``host_sps`` times host-numpy batches in
    the production wire format (every step pays staging + transfer),
    ``step_ms`` is the median blocked per-step device time.  Distinct
    batch permutations are cycled so constant data cannot flatter
    caching.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from handyrl_tpu.learner import _stage_batch
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer, make_update_step

    model, seed_batch, cfg = seed
    wire_cfg = dict(cfg, transfer_dtype="uint8")  # geese planes: binary

    rng = np.random.default_rng(0)
    variants = []
    for _ in range(n_variants):
        perm = rng.permutation(SEED_EPS)
        shuffled = jax.tree.map(lambda v: v[perm], seed_batch)
        variants.append(
            _encode(_tile(shuffled, batch_size // SEED_EPS), wire_cfg))
    resident = [_stage_batch(v, None, compute_dtype) for v in variants]

    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    # fresh copies: the jitted step donates its inputs, and the seed
    # model's params are reused across measurement runs
    params = jax.tree.map(jnp.array, model.params)
    opt_state = optimizer.init(params)
    update = make_update_step(
        model, loss_cfg, optimizer, compute_dtype=compute_dtype)

    params, opt_state, metrics = update(params, opt_state, resident[0])
    float(metrics["total"])  # compile + warmup sync

    # blocked per-step timing: sync every step so the number is the
    # true device latency, not dispatch pipelining
    step_ms = []
    for i in range(timed_iters):
        t0 = time.perf_counter()
        params, opt_state, metrics = update(
            params, opt_state, resident[i % n_variants])
        float(metrics["total"])
        step_ms.append((time.perf_counter() - t0) * 1e3)
    step_ms.sort()
    median_ms = step_ms[len(step_ms) // 2] if step_ms else None

    host_sps = None
    if host_iters:
        t0 = time.perf_counter()
        for i in range(host_iters):
            staged = _stage_batch(
                variants[i % n_variants], None, compute_dtype)
            params, opt_state, metrics = update(params, opt_state, staged)
        float(metrics["total"])  # sync
        host_sps = host_iters / (time.perf_counter() - t0)

    state = {"params": params, "opt_state": opt_state, "i": 0}

    def trial(n=iters):
        params, opt_state = state["params"], state["opt_state"]
        t0 = time.perf_counter()
        for _ in range(n):
            i = state["i"]
            state["i"] += 1
            params, opt_state, metrics = update(
                params, opt_state, resident[i % n_variants])
        float(metrics["total"])  # sync
        sps = n / (time.perf_counter() - t0)
        state["params"], state["opt_state"] = params, opt_state
        return sps

    return trial, host_sps, median_ms


def measure_learner(seed, batch_size, compute_dtype, iters=30,
                    host_iters=5, n_variants=4, timed_iters=10):
    """One-pass form of :func:`setup_learner` (secondary variants)."""
    trial, host_sps, step_ms = setup_learner(
        seed, batch_size, compute_dtype, iters=iters,
        host_iters=host_iters, n_variants=n_variants,
        timed_iters=timed_iters)
    return trial(), host_sps, step_ms


def measure_prefetch(seed, batch_size, compute_dtype, steps=40,
                     n_variants=4):
    """Transfer-pipeline throughput: pre-built host batches in the
    production wire format stream through the threaded DevicePrefetcher
    into the update step.  Isolates H2D staging + compute overlap from
    host-side batch assembly (which scales with host cores)."""
    import queue as _queue

    import jax
    import jax.numpy as jnp
    import numpy as np

    from handyrl_tpu.learner import DevicePrefetcher
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer, make_update_step

    model, seed_batch, cfg = seed
    wire_cfg = dict(cfg, transfer_dtype="uint8")
    rng = np.random.default_rng(1)
    variants = []
    for _ in range(n_variants):
        perm = rng.permutation(SEED_EPS)
        shuffled = jax.tree.map(lambda v: v[perm], seed_batch)
        variants.append(
            _encode(_tile(shuffled, batch_size // SEED_EPS), wire_cfg))

    counter = {"i": 0}

    def source(timeout=None):
        i = counter["i"]
        counter["i"] += 1
        return variants[i % n_variants]

    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    params = jax.tree.map(jnp.array, model.params)
    opt_state = optimizer.init(params)
    update = make_update_step(
        model, loss_cfg, optimizer, compute_dtype=compute_dtype)

    prefetcher = DevicePrefetcher(
        source, depth=3, threads=2, obs_float=compute_dtype)
    batch = prefetcher.get(timeout=120)
    params, opt_state, metrics = update(params, opt_state, batch)
    float(metrics["total"])  # compile + warmup

    t0 = time.perf_counter()
    for _ in range(steps):
        batch = prefetcher.get(timeout=120)
        params, opt_state, metrics = update(params, opt_state, batch)
    float(metrics["total"])
    sps = steps / (time.perf_counter() - t0)
    prefetcher.stop()
    return sps


def setup_pipeline(seed, batch_size, compute_dtype, transfer_dtype,
                   steps=30, depth=3, cfg_over=None, per_step=None):
    """End-to-end learner throughput: batcher processes sampling real
    episodes -> compact wire batches -> threaded device prefetch ->
    update step.  Production training minus the actor plane.

    ``depth`` sets the prefetch queue depth, ``cfg_over`` overrides
    loss-config keys (the lag-tolerance variant uses both: deeper
    queues under `update_algorithm: impact` vs standard — the impact
    step threads its target params through the same trial loop).
    ``per_step`` is an optional host-side callback run once per timed
    step (the durability variant appends episodes to a live WAL there,
    pricing intake-time logging against the training loop).

    Returns (trial, stop, profile): ``trial()`` times ``steps``
    end-to-end steps and may be called repeatedly; batchers and
    prefetch threads stay alive between trials (they quiesce once the
    prefetch queue refills).  Call ``stop()`` when done."""
    from collections import deque

    import jax
    import jax.numpy as jnp

    from handyrl_tpu.learner import Batcher, DevicePrefetcher
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer, make_update_step
    from handyrl_tpu.utils.profiling import SectionTimers

    model, _, cfg, episodes = seed
    cfg = dict(cfg, **(cfg_over or {}))
    args = dict(cfg)
    args.update(
        batch_size=batch_size, num_batchers=2,
        maximum_episodes=len(episodes),
        compute_dtype=compute_dtype, transfer_dtype=transfer_dtype,
    )
    buffer = deque(episodes)
    batcher = Batcher(args, buffer)
    batcher.run()
    prefetcher = DevicePrefetcher(
        batcher.batch, depth=depth, threads=2, obs_float=compute_dtype)

    loss_cfg = LossConfig.from_config(cfg)
    impact = loss_cfg.update_algorithm == "impact"
    optimizer = make_optimizer(1e-3)
    params = jax.tree.map(jnp.array, model.params)
    target = jax.tree.map(jnp.array, model.params) if impact else None
    opt_state = optimizer.init(params)
    update = make_update_step(
        model, loss_cfg, optimizer, compute_dtype=compute_dtype)

    def one_step(params, opt_state, target, batch):
        if impact:
            return update(params, opt_state, batch, target)
        p, o, m = update(params, opt_state, batch)
        return p, o, m, None

    batch = prefetcher.get(timeout=120)
    params, opt_state, metrics, target = one_step(
        params, opt_state, target, batch)
    float(metrics["total"])  # compile + warmup

    timers = SectionTimers()
    state = {"params": params, "opt_state": opt_state, "target": target}

    def trial(n=steps):
        params, opt_state, target = (
            state["params"], state["opt_state"], state["target"])
        t0 = time.perf_counter()
        for _ in range(n):
            with timers.section("batch_wait"):
                batch = prefetcher.get(timeout=120)
            with timers.section("update"):
                params, opt_state, metrics, target = one_step(
                    params, opt_state, target, batch)
            if per_step is not None:
                per_step()
        float(metrics["total"])  # sync
        sps = n / (time.perf_counter() - t0)
        state.update(params=params, opt_state=opt_state, target=target)
        return sps

    def stop():
        prefetcher.stop()
        batcher.shutdown()

    return (trial, stop,
            lambda: {name: v["sec"]
                     for name, v in timers.snapshot().items()})


def lag_tolerance_main(steps=12, depths=(1, 4, 8)):
    """Lag-tolerance variant (one JSON line, like main): sustained e2e
    steps/s as the prefetch queue depth grows, impact-on vs impact-off.

    Deeper queues are how the pipeline work (ROADMAP item 1) buys
    throughput, and they RAISE policy lag by construction — every
    staged batch is one more update the generating snapshot falls
    behind.  This variant prices the IMPACT update step (a second,
    gradient-free target forward) against the standard one at each
    depth: the per-step cost is what the staleness tolerance costs,
    and the depth sweep shows both paths keep their throughput as the
    queue (and therefore the lag) grows.  The learning-side proof that
    impact + `max_policy_lag` actually ABSORB that lag is the chaos
    surge e2e in tests/test_resilience.py."""
    from __graft_entry__ import _build_model_and_batch

    seed4 = _build_model_and_batch(batch_size=SEED_EPS,
                                   return_episodes=True)
    variants = {
        "standard": {},
        "impact": {"update_algorithm": "impact",
                   "target_update_interval": 16},
    }
    results = {}
    for name, over in variants.items():
        per_depth = {}
        for depth in depths:
            trial, stop, prof = setup_pipeline(
                seed4, BATCH, "bfloat16", "uint8", steps=steps,
                depth=depth, cfg_over=over)
            try:
                per_depth[str(depth)] = {
                    "steps_per_sec": round(trial(), 2),
                    "batch_wait_sec": round(
                        prof().get("batch_wait", 0.0), 3),
                }
            finally:
                stop()
        results[name] = per_depth
    base = results["standard"]
    imp = results["impact"]
    overhead = {
        d: round(imp[d]["steps_per_sec"] / base[d]["steps_per_sec"], 3)
        for d in base if d in imp and base[d]["steps_per_sec"]}
    print(json.dumps({
        "metric": "lag_tolerance_steps_per_sec_by_depth",
        "value": imp[str(depths[-1])]["steps_per_sec"],
        "unit": (f"steps/sec (GeeseNet bf16 e2e pipeline, impact, "
                 f"prefetch depth {depths[-1]})"),
        "by_depth": results,
        "impact_vs_standard_by_depth": overhead,
    }))


def durability_main(steps=12, eps_per_step=2):
    """Durability variant (one JSON line, like main): what the
    preemption-proofing costs on the hot paths.

    * checkpoint save/restore latency over a realistic train-state
      blob (params + two params-shaped optimizer moments), checksummed
      write + verified read — the per-epoch price of the manifest
      machinery and the per-resume price of digest verification;
    * WAL append/replay throughput (episodes/s) at the default fsync
      cadence and at fsync-every-append (the paranoid setting);
    * e2e pipeline steps/s with WAL appends interleaved at
      ``eps_per_step`` episodes per step vs without — the number the
      <= 5% overhead budget is judged on.  One pipeline, the hook
      toggled per round, ratios computed PAIRWISE within rounds and
      medianed — same discipline as the headline (a small shared
      host swings far more between trial blocks than the WAL
      costs, so a blocked on-then-off comparison measures drift, not
      overhead; observed 0.26 "overhead" from exactly that).
    """
    import itertools
    import shutil
    import tempfile

    import jax
    import numpy as np

    from __graft_entry__ import _build_model_and_batch
    from handyrl_tpu.durability import (
        EpisodeWAL,
        read_verified,
        write_checksummed,
    )

    seed4 = _build_model_and_batch(batch_size=SEED_EPS,
                                   return_episodes=True)
    model, _, _cfg, episodes = seed4
    work = tempfile.mkdtemp(prefix="bench_durability_")
    try:
        # -- checkpoint save/restore latency --
        params = jax.tree.map(np.asarray, model.params)
        state = {"params": params,
                 "opt_state": [jax.tree.map(np.zeros_like, params),
                               jax.tree.map(np.zeros_like, params)],
                 "steps": 10_000, "epoch": 50}
        ckpt = os.path.join(work, "train_state.ckpt")
        saves, restores = [], []
        for _ in range(5):
            t0 = time.perf_counter()
            write_checksummed(ckpt, state)
            saves.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            read_verified(ckpt)
            restores.append(time.perf_counter() - t0)

        # -- WAL append / replay throughput --
        def wal_eps_per_sec(flush_interval, n=256):
            wal_dir = os.path.join(work, f"wal{flush_interval}")
            wal = EpisodeWAL(wal_dir, flush_interval=flush_interval)
            src = itertools.cycle(episodes)
            t0 = time.perf_counter()
            for _ in range(n):
                wal.append(next(src))
            wal.seal()
            rate = n / (time.perf_counter() - t0)
            t0 = time.perf_counter()
            replayed = sum(1 for _ in wal.replay(set()))
            replay_rate = replayed / (time.perf_counter() - t0)
            wal.close()
            return rate, replay_rate

        append_cadence, replay_rate = wal_eps_per_sec(1.0)
        append_paranoid, _ = wal_eps_per_sec(0.0)

        # -- e2e steps/s, WAL on vs off (interleaved pairwise) --
        wal = EpisodeWAL(os.path.join(work, "wal_live"),
                         flush_interval=1.0)
        live = itertools.cycle(episodes)
        logging = {"on": False}

        def log_intake():
            if logging["on"]:
                for _ in range(eps_per_step):
                    wal.append(next(live))

        trial, stop, _prof = setup_pipeline(
            seed4, BATCH, "bfloat16", "uint8", steps=steps,
            depth=4, per_step=log_intake)

        def leg(wal_on):
            def run():
                logging["on"] = wal_on
                return trial()
            return run

        try:
            runs = _interleaved_rounds(4, {"wal_off": leg(False),
                                           "wal_on": leg(True)})
        finally:
            stop()
        wal.close()
        ratios = _round_ratios(runs["wal_on"], runs["wal_off"])
        rates = {"wal_off": _median(runs["wal_off"]),
                 "wal_on": _median(runs["wal_on"])}
        overhead = 1.0 - _median(ratios) if ratios else 0.0
    finally:
        shutil.rmtree(work, ignore_errors=True)

    print(json.dumps({
        "metric": "durability_wal_overhead_frac",
        "value": round(overhead, 4),
        "unit": (f"1 - steps/s ratio, WAL on ({eps_per_step} eps/step "
                 f"logged) vs off (GeeseNet bf16 e2e pipeline, "
                 f"batch {BATCH}; budget <= 0.05)"),
        "budget_frac": 0.05,
        "steps_per_sec": {k: round(v, 2) for k, v in rates.items()},
        "checkpoint_save_ms": round(_median(saves) * 1e3, 2),
        "checkpoint_restore_ms": round(_median(restores) * 1e3, 2),
        "wal_append_eps_per_sec": round(append_cadence, 1),
        "wal_append_fsync_every_eps_per_sec": round(append_paranoid, 1),
        "wal_replay_eps_per_sec": round(replay_rate, 1),
    }))


def pipeline_train_child(mode, epochs=3):
    """One short REAL-STACK local training (TicTacToe, spawned workers,
    device replay) with the pipelined dataflow on, off, or on-under-
    CHAOS; emits one JSON line of e2e numbers parsed from its
    metrics.jsonl.

    The update budget is capped per epoch so the learner cannot spin
    updates while starved: steps/s then measures how fast the actor
    feed lets the learner cycle epochs — the end-to-end number the
    pipeline exists to move — and `batch_wait` reports the per-epoch
    feed starvation alongside it.

    ``mode: chaos`` is the fault-injection round: pipeline ON with
    the inference service chaos-killed at epoch 1 AND a surge
    brownout (upload hold) mid-measurement — the emitted numbers add
    `recovery_sec` (kill record -> first served-again record) and the
    spill/torn counters, so CI archives how much a real fault costs
    against the clean pipelined round of the same bench run."""
    import shutil
    import tempfile

    work = tempfile.mkdtemp(prefix=f"bench_pipe_{mode}_")
    cwd = os.getcwd()
    os.chdir(work)
    try:
        args = {
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                "turn_based_training": True, "observation": False,
                "gamma": 0.8, "forward_steps": 8, "burn_in_steps": 0,
                "compress_steps": 4, "entropy_regularization": 0.1,
                "entropy_regularization_decay": 0.1,
                "update_episodes": 60, "batch_size": 64,
                "minimum_episodes": 40, "maximum_episodes": 400,
                "epochs": epochs, "num_batchers": 1, "eval_rate": 0.05,
                "updates_per_epoch": 40,
                "worker": {"num_parallel": 2}, "lambda": 0.7,
                "policy_target": "VTRACE", "value_target": "VTRACE",
                "seed": 3, "metrics_path": "metrics.jsonl",
                "telemetry": False,  # measure the dataflow, not spans
                "pipeline": {"mode": "on" if mode == "chaos"
                             else mode},
            },
            "worker_args": {"num_parallel": 2, "server_address": ""},
        }
        if mode == "chaos":
            # service kill + brownout mid-measurement: the respawn
            # backoff is pinned so recovery_sec measures the ladder
            # (stale board -> local fallback -> respawn -> served
            # again), not a knob
            args["train_args"]["respawn_backoff"] = 0.5
            args["train_args"]["chaos"] = {
                "infer_kill_epoch": 1,
                "surge_epoch": 1, "surge_hold_uploads": 2.0,
                "seed": 3,
            }
        from handyrl_tpu.learner import Learner

        learner = Learner(args)
        learner.run()
        with open("metrics.jsonl") as f:
            recs = [json.loads(line) for line in f if line.strip()]
    finally:
        os.chdir(cwd)
        shutil.rmtree(work, ignore_errors=True)

    dt = recs[-1]["time_sec"] - recs[0]["time_sec"]
    steps = recs[-1]["steps"] - recs[0]["steps"]
    post = recs[1:]  # the first window pays compile + worker bring-up
    out = {
        "mode": mode,
        "steps_per_sec_e2e": round(steps / dt, 2) if dt > 0 else None,
        "eps_per_sec_e2e": round(
            60.0 * (len(recs) - 1) / dt, 2) if dt > 0 else None,
        "batch_wait_sec": round(
            sum(r.get("batch_wait_sec", 0.0) for r in post) / len(post),
            4),
        "epoch_wall_sec": round(
            sum(r["epoch_wall_sec"] for r in post) / len(post), 3),
    }
    if mode in ("on", "chaos"):
        served = [r for r in recs if r.get("infer_batches", 0) > 0]
        out["infer_batch_size_mean"] = round(sum(
            r["infer_batch_size_mean"] for r in served)
            / len(served), 2) if served else None
        out["infer_queue_wait_sec"] = round(sum(
            r["infer_queue_wait_sec"] for r in served)
            / len(served), 6) if served else None
        out["shm_ring_full_count"] = recs[-1].get("shm_ring_full_count")
        out["infer_respawns"] = recs[-1].get("infer_respawns")
    if mode == "chaos":
        # recovery time: the kill fires inside the update() that
        # advances the model to `infer_kill_epoch` — i.e. at the
        # boundary that WRITES the (kill_epoch - 1) record — so the
        # gap from that record to the first record that both
        # respawned AND dispatched served batches is the fault's
        # visible footprint (epoch-granular, an upper bound)
        kill_epoch = args["train_args"]["chaos"]["infer_kill_epoch"]
        kill_t = next((r["time_sec"] for r in recs
                       if r["epoch"] == kill_epoch - 1), None)
        back_t = next((r["time_sec"] for r in recs
                       if r.get("infer_respawns", 0) >= 1
                       and r.get("infer_batches", 0) > 0), None)
        out["recovery_sec"] = (round(back_t - kill_t, 3)
                               if kill_t is not None
                               and back_t is not None else None)
        out["episodes_spilled"] = sum(
            r.get("episodes_spilled", 0) for r in recs)
        out["shm_torn_slots"] = recs[-1].get("shm_torn_slots")
    print(json.dumps(out))
    sys.stdout.flush()
    os._exit(0)  # skip non-daemonic gather joins (intake_child idiom)


def pipeline_main(rounds=3, epochs=3):
    """Pipeline variant (one JSON line, like main): the REAL worker/
    learner stack with pipelined inference + shm trajectories vs the
    legacy per-worker path, INTERLEAVED pairwise per round and ratioed
    within rounds — the same discipline as `--durability` (this host
    swings far more between trial blocks than either path's margin).

    Each round also runs a CHAOS leg: pipeline on with the inference
    service killed and a surge brownout mid-measurement.  The JSON
    reports the recovery time and the chaos/clean steps/s degradation
    ratio next to the clean speedup, so a regression in the
    degradation ladder (slow respawn, stuck fallback, spill storms)
    moves a number CI archives."""
    runs = _interleaved_rounds(rounds, {
        "legacy": lambda: _run_child("--pipeline-child", timeout=900,
                                     extra=["off", str(epochs)],
                                     on_chip=True),
        "pipelined": lambda: _run_child("--pipeline-child", timeout=900,
                                        extra=["on", str(epochs)],
                                        on_chip=True),
        "chaos": lambda: _run_child("--pipeline-child", timeout=900,
                                    extra=["chaos", str(epochs)],
                                    on_chip=True),
    })
    legacy, piped, ratios, waits_l, waits_p = [], [], [], [], []
    chaos_sps, chaos_deg, recovery = [], [], []
    extras = {}
    for off, on, chaos in zip(runs["legacy"], runs["pipelined"],
                              runs["chaos"]):
        if off.get("steps_per_sec_e2e") and on.get("steps_per_sec_e2e"):
            legacy.append(off["steps_per_sec_e2e"])
            piped.append(on["steps_per_sec_e2e"])
            ratios.append(on["steps_per_sec_e2e"]
                          / off["steps_per_sec_e2e"])
            waits_l.append(off["batch_wait_sec"])
            waits_p.append(on["batch_wait_sec"])
            for k in ("infer_batch_size_mean", "infer_queue_wait_sec",
                      "shm_ring_full_count", "infer_respawns"):
                if on.get(k) is not None:
                    extras.setdefault(k, []).append(on[k])
            if chaos.get("steps_per_sec_e2e"):
                chaos_sps.append(chaos["steps_per_sec_e2e"])
                chaos_deg.append(chaos["steps_per_sec_e2e"]
                                 / on["steps_per_sec_e2e"])
            if chaos.get("recovery_sec") is not None:
                recovery.append(chaos["recovery_sec"])
    if not ratios:
        print(json.dumps({"metric": "pipeline_e2e_speedup",
                          "error": "no complete rounds"}))
        return
    chaos_out = {}
    if chaos_sps:
        chaos_out = {
            "learner_steps_per_sec_e2e_chaos": round(
                _median(chaos_sps), 2),
            # chaos / clean-pipelined steps/s within the same round:
            # what the kill + brownout cost end to end (1.0 = free)
            "chaos_degradation": round(_median(chaos_deg), 3),
        }
    if recovery:
        chaos_out["chaos_recovery_sec"] = round(_median(recovery), 3)
    print(json.dumps({
        "metric": "pipeline_e2e_speedup",
        "value": round(_median(ratios), 3),
        "unit": ("pipelined / legacy e2e learner steps/s ratio "
                 "(TicTacToe real stack, 2 workers, "
                 f"median of {len(ratios)} interleaved rounds; "
                 "chaos leg = service kill + surge brownout)"),
        "learner_steps_per_sec_e2e_pipelined": round(_median(piped), 2),
        "learner_steps_per_sec_e2e_legacy": round(_median(legacy), 2),
        "e2e_batch_wait_sec_pipelined": round(_median(waits_p), 4),
        "e2e_batch_wait_sec_legacy": round(_median(waits_l), 4),
        **{k: _median(v) for k, v in extras.items()},
        **chaos_out,
        "rounds": {"pipelined": piped, "legacy": legacy,
                   "chaos": chaos_sps,
                   "ratios": [round(r, 3) for r in ratios]},
    }))


def serve_child(mode, seconds=6.0, clients=12):
    """One serving-tier load leg (a subprocess, pinned to CPU like
    production): a standalone InferenceService + ServingFrontend on an
    ephemeral port, hammered by ``clients`` closed-loop client threads
    for ``seconds``; emits one JSON line of client-side RPS + latency
    percentiles and server-side reconciliation counters.

    Modes: ``batched`` (the continuous-batching window aggregates all
    clients into one jitted forward), ``unbatched`` (max_batch 1 —
    one forward per request, the naive per-request server this tier
    replaces; the acceptance gate is batched >= 2x this), ``chaos``
    (batched, with the inference service CHAOS-KILLED mid-load and
    respawned behind a 0.5s backoff — shed/failed requests must
    reconcile EXACTLY against submitted ones and serving must resume),
    and ``openloop`` (fixed-rate arrivals against a small
    ``max_inflight`` so admission control sheds visibly instead of
    letting latency collapse), and ``mesh`` (batched, but the service
    dispatch runs as ONE GSPMD program over a virtual device mesh —
    the leg reports the sharded-vs-unsharded dispatch cost at the
    forward itself; the parent arms 8 fake CPU devices via
    XLA_FLAGS)."""
    import threading

    from handyrl_tpu.connection import force_cpu_jax

    force_cpu_jax()

    import numpy as np

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.pipeline import InferenceService, PipelineConfig
    from handyrl_tpu.serving import ServingConfig, ServingFrontend

    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=0)
    obs = env.observation(env.players()[0])

    batched = mode != "unbatched"
    pcfg = PipelineConfig.from_config({
        "mode": "on",
        "batch_window": 0.002 if batched else 0.0,
        "max_batch": 64 if batched else 1,
    })

    # service-level batching gate, measured at the jitted forward
    # itself: answering `clients` requests costs ONE bucket-padded
    # forward batched vs `clients` batch-1 dispatches per-request.
    # This isolates what the batching window buys from load-generator
    # contamination — on this 1-core container the e2e closed-loop
    # ratio below is bounded by per-request socket/thread costs that
    # no server architecture can remove (and compute itself is batch-
    # linear without parallel hardware), while an accelerator host
    # realizes this factor nearly in full (batch-N ~ batch-1 there)
    import jax as _jax

    from handyrl_tpu.pipeline.service import _bucket

    def _fwd_ms(rows, reps=40):
        b = _jax.tree.map(
            lambda a: np.stack([np.asarray(a)] * rows), obs)
        model.inference_batch(b, None)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            model.inference_batch(b, None)
        return (time.perf_counter() - t0) / reps * 1e3

    t_one = _fwd_ms(1)
    t_bucket = _fwd_ms(_bucket(clients, 64))
    amortization = clients * t_one / t_bucket if t_bucket else None
    scfg = ServingConfig.from_config({
        "mode": "on", "port": 0, "reply_timeout": 3.0,
        # throughput legs measure the dataflow, not the SLO machinery;
        # the open-loop leg arms a tight admission cap instead so the
        # shedding path is what gets measured
        "slo_ms": 0.0,
        "max_inflight": 4 if mode == "openloop" else 256,
    })
    mesh = None
    if mode == "mesh":
        from handyrl_tpu.parallel import MeshSpec, make_mesh

        n_dev = len(_jax.devices())
        if n_dev >= 8:
            mesh = make_mesh(MeshSpec(dp=4, tp=2))
        elif n_dev >= 2:
            mesh = make_mesh(MeshSpec(dp=n_dev))
    svc = InferenceService(model, pcfg, epoch=1, mesh=mesh)
    mesh_fwd_ms = None
    if mesh is not None:
        # the sharded dispatch cost, measured at the service's OWN
        # guarded forward on the same bucket the batched leg uses —
        # ratioed against the unsharded bucket forward above.  On this
        # CPU host the partition overhead is the whole story (no
        # parallel hardware); on an accelerator mesh the same ratio is
        # what tensor-sharded serving of too-big nets costs per row
        rows = _bucket(clients, 64)
        b = _jax.tree.map(
            lambda a: np.stack([np.asarray(a)] * rows), obs)
        svc._forward(model, b)  # compile outside the window
        t0 = time.perf_counter()
        for _ in range(40):
            svc._forward(model, b)
        mesh_fwd_ms = (time.perf_counter() - t0) / 40 * 1e3
    svc.start()
    frontend = ServingFrontend(svc, env, scfg)
    frontend.start()

    warm = max(2.5, 0.3 * seconds)  # jit buckets compile off-window
    t_start = time.monotonic()
    t_measure = t_start + warm
    t_end = t_measure + seconds
    stop = threading.Event()
    # open-loop offered rate: deliberately ABOVE what max_inflight 4
    # admits at this host's per-request latency, so the leg shows
    # admission shedding (typed, counted) instead of latency collapse
    rate_interval = clients / 1500.0 if mode == "openloop" else 0.0

    # load generator: the request frame is PRE-ENCODED once and the
    # loop is raw socket I/O + one reply unpickle — a load generator
    # sharing the server's (single) core must not bill its own
    # request-pickling to the server under test.  (Real consumers use
    # ServeClient — the typed-outcome e2e tests do; the wire bytes
    # here are identical.)
    import pickle as _pickle
    import struct as _struct

    row = np.asarray(obs)[None]
    req_payload = _pickle.dumps(("infer", {"obs": row, "epoch": None}),
                                protocol=_pickle.HIGHEST_PROTOCOL)
    req_frame = _struct.pack("!I", len(req_payload)) + req_payload
    import socket as _socket

    def _recv_reply(sock):
        buf = b""
        while len(buf) < 4:
            chunk = sock.recv(4 - len(buf))
            if not chunk:
                raise ConnectionError("peer closed")  # EOF, not a spin
            buf += chunk
        (n,) = _struct.unpack("!I", buf)
        body = bytearray()
        while len(body) < n:
            chunk = sock.recv(n - len(body))
            if not chunk:
                raise ConnectionError("peer closed mid-reply")
            body += chunk
        return _pickle.loads(bytes(body))

    def load(idx, out):
        sock = None
        ok = shed = errors = drops = 0
        lats = []
        next_t = time.monotonic() + idx * (rate_interval / clients
                                           if rate_interval else 0.0)
        while not stop.is_set() and time.monotonic() < t_end:
            if rate_interval:
                # open loop: fixed-rate arrivals, not completion-paced
                next_t += rate_interval
                delay = next_t - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
            try:
                if sock is None:
                    sock = _socket.create_connection(
                        ("127.0.0.1", frontend.port), timeout=5.0)
                t0 = time.perf_counter()
                sock.sendall(req_frame)
                reply = _recv_reply(sock)
                dt_ms = (time.perf_counter() - t0) * 1e3
                if time.monotonic() < t_measure:
                    continue
                status = reply.get("status")
                if status == "ok":
                    ok += 1
                    lats.append(dt_ms)
                elif status == "shed":
                    shed += 1
                else:
                    errors += 1
            except Exception:
                drops += 1  # conn severed (frontend churn): redial
                if sock is not None:
                    sock.close()
                sock = None
                time.sleep(0.05)
        if sock is not None:
            sock.close()
        out[idx] = {"ok": ok, "shed": shed, "errors": errors,
                    "drops": drops, "lats": lats}

    results = {}
    threads = [threading.Thread(target=load, args=(i, results),
                                daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()

    respawns = 0
    ok_at_respawn = None
    if mode == "chaos":
        # kill mid-load, then the learner's respawn ladder in
        # miniature: 0.5s backoff, same service object, new incarnation
        time.sleep(warm + 0.35 * seconds)
        svc.inject_kill()
        while svc.alive:
            time.sleep(0.01)
        time.sleep(0.5)
        svc.set_model(model, 1)
        svc.respawn()
        respawns += 1
        ok_at_respawn = frontend.stats()["ok"]
    for t in threads:
        t.join(timeout=warm + seconds + 15)
    stop.set()
    # settle: a client that timed out client-side may have left a
    # handler still waiting out reply_timeout — its terminal count
    # must land before the reconciliation check reads the counters
    time.sleep(scfg.reply_timeout + 0.5)

    stats = frontend.stats()
    lats = sorted(l for r in results.values() for l in r["lats"])
    ok = sum(r["ok"] for r in results.values())
    out = {
        "mode": mode,
        "clients": clients,
        "rps": round(ok / seconds, 1),
        "ok": ok,
        "shed": sum(r["shed"] for r in results.values()),
        "errors": sum(r["errors"] for r in results.values()),
        "conn_drops": sum(r["drops"] for r in results.values()),
        "p50_ms": round(lats[len(lats) // 2], 3) if lats else None,
        "p99_ms": round(lats[min(len(lats) - 1,
                                 int(0.99 * len(lats)))], 3)
        if lats else None,
        # server-side reconciliation: every arrival is accounted as
        # exactly one of ok/shed/error — the no-silent-loss invariant
        "submitted": stats["submitted"],
        "reconciled": stats["submitted"]
        == stats["ok"] + stats["shed"] + stats["errors"],
        "shed_by": stats["shed_by"],
        "service_fwd_ms_batch1": round(t_one, 4),
        "service_fwd_ms_bucket": round(t_bucket, 4),
        "service_amortization_x": (round(amortization, 2)
                                   if amortization else None),
    }
    if mode == "mesh":
        out["mesh_devices"] = svc.stats()["mesh_devices"]
        out["infer_resharding_copies"] = svc.shard_guard.copies
        if mesh_fwd_ms is not None and t_bucket:
            out["mesh_fwd_ms_bucket"] = round(mesh_fwd_ms, 4)
            out["mesh_dispatch_cost_x"] = round(
                mesh_fwd_ms / t_bucket, 3)
    if mode == "chaos":
        out["respawns"] = respawns
        out["resumed_after_respawn"] = (
            ok_at_respawn is not None
            and stats["ok"] > ok_at_respawn)
    frontend.close()
    svc.close()
    print(json.dumps(out))
    sys.stdout.flush()
    os._exit(0)


def serve_main(rounds=2):
    """Serving variant (one JSON line, like main): closed-loop RPS +
    p50/p99 of the continuous-batching network frontend vs the
    unbatched per-request baseline on the same host, interleaved
    pairwise per round (the shared `_interleaved_rounds` discipline),
    plus a chaos leg (inference-service kill mid-load: exact
    shed/failed reconciliation + served-again proof) and an open-loop
    leg (fixed-rate arrivals shedding under a tight admission cap
    instead of collapsing latency)."""
    runs = _interleaved_rounds(rounds, {
        "unbatched": lambda: _run_child("--serve-child", timeout=600,
                                        extra=["unbatched"]),
        "batched": lambda: _run_child("--serve-child", timeout=600,
                                      extra=["batched"]),
        "chaos": lambda: _run_child("--serve-child", timeout=600,
                                    extra=["chaos"]),
        "openloop": lambda: _run_child("--serve-child", timeout=600,
                                       extra=["openloop"]),
        # GSPMD leg: the same batched load, but the dispatch runs as
        # one sharded program over 8 virtual devices — reports the
        # sharded-vs-unsharded forward cost (mesh_dispatch_cost_x)
        "mesh": lambda: _run_child(
            "--serve-child", timeout=600, extra=["mesh"],
            env_extra={"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=8"}),
    })
    ratios = _round_ratios(runs["batched"], runs["unbatched"],
                           key="rps")
    if not ratios:
        print(json.dumps({"metric": "serving_batched_vs_unbatched_rps",
                          "error": "no complete rounds"}))
        return
    batched = [r for r in runs["batched"] if r.get("rps")]
    unbatched = [r for r in runs["unbatched"] if r.get("rps")]
    chaos = [r for r in runs["chaos"] if r.get("submitted")]
    openloop = [r for r in runs["openloop"] if r.get("submitted")]
    amort = [r["service_amortization_x"]
             for r in batched + unbatched
             if r.get("service_amortization_x")]
    out = {
        "metric": "serving_batched_vs_unbatched",
        # the gate: answering one window's worth of requests costs one
        # bucket-padded forward batched vs `clients` batch-1 dispatches
        # per-request — measured AT THE SERVICE on this host (>= 2).
        # The closed-loop e2e RPS ratio rides below; on a 1-core
        # container it is bounded by per-request socket/thread costs
        # shared by BOTH legs (and compute is batch-linear with no
        # parallel hardware), the same caveat family as
        # bench_pipeline's "this host can't show the accelerator win"
        "value": round(_median(amort), 2) if amort else None,
        "unit": ("per-request forward cost, batched (one bucket-padded "
                 "dispatch) / unbatched (batch-1 dispatch each), "
                 "TicTacToe net, 12 network clients, median of "
                 f"{len(ratios)} interleaved rounds; gate >= 2"),
        "closed_loop_rps_ratio": round(_median(ratios), 3),
        "serve_rps_batched": _median([r["rps"] for r in batched]),
        "serve_rps_unbatched": _median([r["rps"] for r in unbatched]),
        "serve_p50_ms_batched": _median(
            [r["p50_ms"] for r in batched if r.get("p50_ms")]),
        "serve_p99_ms_batched": _median(
            [r["p99_ms"] for r in batched if r.get("p99_ms")]),
        "rounds": {"batched": [r["rps"] for r in batched],
                   "unbatched": [r["rps"] for r in unbatched],
                   "ratios": [round(r, 3) for r in ratios]},
    }
    if chaos:
        out["chaos_reconciled"] = all(r.get("reconciled")
                                      for r in chaos)
        out["chaos_resumed_after_respawn"] = all(
            r.get("resumed_after_respawn") for r in chaos)
        out["chaos_rps"] = _median([r["rps"] for r in chaos])
        out["chaos_shed"] = _median([r["shed"] for r in chaos])
        out["chaos_errors"] = _median([r["errors"] for r in chaos])
    if openloop:
        shed_frac = [r["shed"] / max(1, r["shed"] + r["ok"])
                     for r in openloop]
        out["openloop_shed_frac"] = round(_median(shed_frac), 3)
        out["openloop_rps"] = _median([r["rps"] for r in openloop])
        out["openloop_p99_ms"] = _median(
            [r["p99_ms"] for r in openloop if r.get("p99_ms")])
        out["openloop_reconciled"] = all(r.get("reconciled")
                                         for r in openloop)
    mesh_leg = [r for r in runs.get("mesh", []) if r.get("rps")]
    if mesh_leg:
        out["mesh_rps"] = _median([r["rps"] for r in mesh_leg])
        out["mesh_devices"] = mesh_leg[0].get("mesh_devices")
        costs = [r["mesh_dispatch_cost_x"] for r in mesh_leg
                 if r.get("mesh_dispatch_cost_x")]
        if costs:
            # sharded/unsharded per-dispatch forward cost at the
            # bucket (CPU: pure partition overhead; accelerator: what
            # tensor-sharded serving of a too-big net costs per row)
            out["mesh_dispatch_cost_x"] = round(_median(costs), 3)
        out["mesh_resharding_copies"] = max(
            r.get("infer_resharding_copies", 0) for r in mesh_leg)
    print(json.dumps(out))


def router_child(mode, seconds=6.0, clients=12):
    """One pool-routing load leg (a subprocess, like serve_child):
    ``single`` runs one ServingFrontend hit directly (the baseline);
    ``pool`` runs TWO frontends registered into a RouterFrontend via
    real ReplicaAnnouncers, with clients hammering the router's one
    endpoint; ``chaos`` is the pool leg plus a mid-load silent kill of
    one replica (frontend + announcer, no goodbye) — emitting
    ``recovery_sec`` (kill -> next routed ok), ``eviction_sec`` (kill
    -> registry sweep eviction, gated by heartbeat_timeout), the exact
    ``submitted == ok + shed + errors`` reconciliation at the router,
    and the respawned replica's registry generation bump."""
    import threading

    from handyrl_tpu.connection import force_cpu_jax

    force_cpu_jax()

    import numpy as np

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.pipeline import InferenceService, PipelineConfig
    from handyrl_tpu.serving import ReplicaAnnouncer, RouterConfig, \
        RouterFrontend, ServingConfig, ServingFrontend

    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=0)
    obs = env.observation(env.players()[0])

    pcfg = PipelineConfig.from_config(
        {"mode": "on", "batch_window": 0.002, "max_batch": 64})
    scfg = ServingConfig.from_config({
        "mode": "on", "port": 0, "reply_timeout": 3.0, "slo_ms": 0.0})
    svc = InferenceService(model, pcfg, epoch=1)
    svc.start()

    n_replicas = 1 if mode == "single" else 2
    # both replicas share ONE inference service (one jit on this
    # single-core host): the leg measures the ROUTING plane — spread,
    # eviction, re-route — not duplicated model compute
    frontends = [ServingFrontend(svc, env, scfg)
                 for _ in range(n_replicas)]
    for fe in frontends:
        fe.start()

    router = None
    announcers = []
    if mode == "single":
        target_port = frontends[0].port
    else:
        rcfg = RouterConfig.from_config({
            "mode": "on", "port": 0,
            # tight cadence so the chaos leg's sweep eviction lands
            # inside the measurement window
            "heartbeat_interval": 0.25, "heartbeat_timeout": 1.0,
            "reply_timeout": 3.0,
            # strictest breaker: the first transport failure against a
            # replica drains it until its next heartbeat
            "replica_failures": 0, "failure_window": 5.0})
        router = RouterFrontend(rcfg)
        router.start()
        for i, fe in enumerate(frontends):
            ann = ReplicaAnnouncer(
                "127.0.0.1", router.port, f"replica-{i}",
                (lambda fe=fe: fe.advert(epochs=(1,))),
                interval=rcfg.heartbeat_interval)
            ann.start()
            announcers.append(ann)
        deadline = time.monotonic() + 10.0
        while (router.registry.pool_size() < n_replicas
               and time.monotonic() < deadline):
            time.sleep(0.02)
        target_port = router.port

    warm = max(2.5, 0.3 * seconds)
    t_start = time.monotonic()
    t_measure = t_start + warm
    t_end = t_measure + seconds
    stop = threading.Event()

    import pickle as _pickle
    import socket as _socket
    import struct as _struct

    row = np.asarray(obs)[None]
    req_payload = _pickle.dumps(("infer", {"obs": row, "epoch": None}),
                                protocol=_pickle.HIGHEST_PROTOCOL)
    req_frame = _struct.pack("!I", len(req_payload)) + req_payload

    def _recv_reply(sock):
        buf = b""
        while len(buf) < 4:
            chunk = sock.recv(4 - len(buf))
            if not chunk:
                raise ConnectionError("peer closed")
            buf += chunk
        (n,) = _struct.unpack("!I", buf)
        body = bytearray()
        while len(body) < n:
            chunk = sock.recv(n - len(body))
            if not chunk:
                raise ConnectionError("peer closed mid-reply")
            body += chunk
        return _pickle.loads(bytes(body))

    def load(idx, out):
        sock = None
        ok = shed = errors = drops = 0
        lats = []
        while not stop.is_set() and time.monotonic() < t_end:
            try:
                if sock is None:
                    sock = _socket.create_connection(
                        ("127.0.0.1", target_port), timeout=5.0)
                t0 = time.perf_counter()
                sock.sendall(req_frame)
                reply = _recv_reply(sock)
                dt_ms = (time.perf_counter() - t0) * 1e3
                if time.monotonic() < t_measure:
                    continue
                status = reply.get("status")
                if status == "ok":
                    ok += 1
                    lats.append(dt_ms)
                elif status == "shed":
                    shed += 1
                else:
                    errors += 1
            except Exception:
                drops += 1
                if sock is not None:
                    sock.close()
                sock = None
                time.sleep(0.05)
        if sock is not None:
            sock.close()
        out[idx] = {"ok": ok, "shed": shed, "errors": errors,
                    "drops": drops, "lats": lats}

    results = {}
    threads = [threading.Thread(target=load, args=(i, results),
                                daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()

    chaos_out = {}
    if mode == "chaos":
        time.sleep(warm + 0.35 * seconds)
        victim_fe, victim_ann = frontends[1], announcers[1]
        ok_at_kill = router.stats()["ok"]
        t_kill = time.monotonic()
        # silent death: no drain, no goodbye — the router must learn
        # from transport failures (immediate suspect-drain + re-route)
        # and from missing heartbeats (sweep eviction)
        victim_ann.kill()
        victim_fe.inject_kill()
        while (router.stats()["ok"] <= ok_at_kill
               and time.monotonic() < t_end):
            time.sleep(0.005)
        recovery_sec = time.monotonic() - t_kill
        while (router.registry.pool_size() > 1
               and time.monotonic() < t_end):
            time.sleep(0.02)
        eviction_sec = time.monotonic() - t_kill
        # respawn: fresh listener, fresh announcer loop — the
        # re-register under the same name bumps the generation
        victim_fe.respawn()
        victim_ann.respawn()
        deadline = time.monotonic() + 10.0
        while (router.registry.generation("replica-1") != 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        chaos_out = {
            "recovery_sec": round(recovery_sec, 3),
            "eviction_sec": round(eviction_sec, 3),
            "evicted_within_timeout": eviction_sec
            <= rcfg.heartbeat_timeout + 2 * router.ACCEPT_TIMEOUT,
            "generation_bump":
                router.registry.generation("replica-1") == 1,
            "pool_recovered": router.registry.pool_size() == 2,
        }
    for t in threads:
        t.join(timeout=warm + seconds + 15)
    stop.set()
    time.sleep(scfg.reply_timeout + 0.5)

    stats = router.stats() if router is not None else \
        frontends[0].stats()
    lats = sorted(l for r in results.values() for l in r["lats"])
    ok = sum(r["ok"] for r in results.values())
    out = {
        "mode": mode,
        "clients": clients,
        "replicas": n_replicas,
        "rps": round(ok / seconds, 1),
        "ok": ok,
        "shed": sum(r["shed"] for r in results.values()),
        "errors": sum(r["errors"] for r in results.values()),
        "conn_drops": sum(r["drops"] for r in results.values()),
        "p50_ms": round(lats[len(lats) // 2], 3) if lats else None,
        "p99_ms": round(lats[min(len(lats) - 1,
                                 int(0.99 * len(lats)))], 3)
        if lats else None,
        # router-side (or frontend-side, single) reconciliation: every
        # arrival accounted as exactly one of ok/shed/error
        "submitted": stats["submitted"],
        "reconciled": stats["submitted"]
        == stats["ok"] + stats["shed"] + stats["errors"],
        **chaos_out,
    }
    if router is not None:
        out["reroutes"] = stats["reroutes"]
        out["pool_sheds"] = stats["pool_sheds"]
        out["evictions"] = stats["registry"]["evictions"]
    for ann in announcers:
        ann.close(drain=False)
    if router is not None:
        router.close()
    for fe in frontends:
        fe.close()
    svc.close()
    print(json.dumps(out))
    sys.stdout.flush()
    os._exit(0)


def router_main(rounds=2):
    """Pool-routing variant (one JSON line, like main): closed-loop
    RPS of a 2-replica pool behind the router vs one frontend hit
    directly, interleaved pairwise per round (the shared
    `_interleaved_rounds` discipline), plus a chaos leg (silent kill
    of one replica mid-load: recovery_sec to the next routed ok,
    sweep eviction inside the heartbeat timeout, exact reconciliation
    at the router, and the respawn's registry generation bump)."""
    runs = _interleaved_rounds(rounds, {
        "single": lambda: _run_child("--router-child", timeout=600,
                                     extra=["single"]),
        "pool": lambda: _run_child("--router-child", timeout=600,
                                   extra=["pool"]),
        "chaos": lambda: _run_child("--router-child", timeout=600,
                                    extra=["chaos"]),
    })
    ratios = _round_ratios(runs["pool"], runs["single"], key="rps")
    if not ratios:
        print(json.dumps({"metric": "router_pool_vs_single_rps",
                          "error": "no complete rounds"}))
        return
    pool = [r for r in runs["pool"] if r.get("rps")]
    single = [r for r in runs["single"] if r.get("rps")]
    chaos = [r for r in runs["chaos"] if r.get("submitted")]
    out = {
        "metric": "router_pool_vs_single",
        # the routed-path cost/benefit on THIS host: both legs share
        # one core and one inference service, so the ratio isolates
        # the router hop (a pool of real hosts adds their compute;
        # the chaos keys below are the numbers this subsystem is FOR)
        "value": round(_median(ratios), 3),
        "unit": ("closed-loop RPS, 2-replica pool behind the router / "
                 "one frontend direct, TicTacToe net, 12 clients, "
                 f"median of {len(ratios)} interleaved rounds; "
                 "chaos leg = silent replica kill -> re-route + "
                 "sweep eviction + respawn generation bump"),
        "pool_rps": _median([r["rps"] for r in pool]),
        "single_rps": _median([r["rps"] for r in single]),
        "pool_p99_ms": _median(
            [r["p99_ms"] for r in pool if r.get("p99_ms")]),
        "pool_reconciled": all(r.get("reconciled") for r in pool),
        "rounds": {"pool": [r["rps"] for r in pool],
                   "single": [r["rps"] for r in single],
                   "ratios": [round(r, 3) for r in ratios]},
    }
    if chaos:
        out["chaos_reconciled"] = all(r.get("reconciled")
                                      for r in chaos)
        out["chaos_recovery_sec"] = _median(
            [r["recovery_sec"] for r in chaos
             if r.get("recovery_sec") is not None])
        out["chaos_eviction_sec"] = _median(
            [r["eviction_sec"] for r in chaos
             if r.get("eviction_sec") is not None])
        out["chaos_evicted_within_timeout"] = all(
            r.get("evicted_within_timeout") for r in chaos)
        out["chaos_generation_bump"] = all(
            r.get("generation_bump") for r in chaos)
        out["chaos_pool_recovered"] = all(
            r.get("pool_recovered") for r in chaos)
        out["chaos_rps"] = _median([r["rps"] for r in chaos])
    print(json.dumps(out))


ANAKIN_TRAIN_ARGS = {
    "turn_based_training": True, "observation": False,
    "gamma": 0.8, "forward_steps": 8, "burn_in_steps": 0,
    "compress_steps": 4, "entropy_regularization": 0.1,
    "entropy_regularization_decay": 0.1,
    "update_episodes": 60, "batch_size": 64,
    "minimum_episodes": 40, "maximum_episodes": 400,
    "num_batchers": 1, "eval_rate": 0.05,
    "lambda": 0.7, "policy_target": "VTRACE",
    "value_target": "VTRACE", "seed": 3,
    "metrics_path": "metrics.jsonl",
    "telemetry": False,  # measure the dataflow, not spans
    # pinned OFF now that the repo default is on: this bench defines
    # the fused-loop vs HOST-ACTOR-IMPALA comparison (the recorded
    # 69.5x baseline and the >= 10x CI gate) — letting the host leg
    # silently become pipelined would change the ratio's meaning
    "pipeline": {"mode": "off"},
}


def _anakin_engine(num_envs, seed=3):
    """A standalone fused-rollout engine (ceiling measurements)."""
    from handyrl_tpu.anakin import AnakinConfig, AnakinEngine
    from handyrl_tpu.environment import make_env, make_jax_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer

    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=seed)
    cfg = dict(ANAKIN_TRAIN_ARGS, eval={"opponent": ["random"]})
    engine = AnakinEngine(
        make_jax_env({"env": "TicTacToe"}), model,
        LossConfig.from_config(cfg), make_optimizer(1e-3),
        AnakinConfig.from_config({"mode": "on", "num_envs": num_envs}),
        seed=seed)
    return engine, model


def anakin_train_child(epochs=3, num_envs=512, updates_per_epoch=8,
                       mesh=False):
    """Real-Learner training in Anakin mode; emits one JSON line of
    steady-state fused throughput plus the acceptance-guard counters.

    Steady state skips the first epoch (it pays the fused-step compile
    and worker bring-up).  The child HARD-ASSERTS the fused step's
    contract — exactly one compile across the run and zero resharding
    copies, straight from the per-epoch guard metrics — so a shape or
    layout regression fails the bench, not just dents the number.
    After the run it also times the rollout alone (one extra jit): the
    engine's GENERATION ceiling with no update attached, the
    apples-to-apples twin of the host pool microbenchmark."""
    import shutil
    import tempfile

    work = tempfile.mkdtemp(prefix="bench_anakin_")
    cwd = os.getcwd()
    os.chdir(work)
    try:
        args = {
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                **ANAKIN_TRAIN_ARGS, "epochs": epochs,
                "updates_per_epoch": updates_per_epoch,
                "worker": {"num_parallel": 1},
                "max_update_compiles": 1, "max_resharding_copies": 1,
                "anakin": {"mode": "on", "num_envs": num_envs},
                # the mesh leg: the fused step runs GSPMD over the
                # parent-armed virtual devices (dp4 x tp2) — same
                # guard contract, env axis sharded on dp
                **({"mesh": {"dp": 4, "tp": 2}} if mesh else {}),
            },
            "worker_args": {"num_parallel": 1, "server_address": ""},
        }
        from handyrl_tpu.learner import Learner

        Learner(args).run()
        with open("metrics.jsonl") as f:
            recs = [json.loads(line) for line in f if line.strip()]
    finally:
        os.chdir(cwd)
        shutil.rmtree(work, ignore_errors=True)

    for rec in recs:
        assert rec["retrace_count"] == 1, (
            f"fused step compiled {rec['retrace_count']}x "
            f"(epoch {rec['epoch']}): shape churn in the hot loop")
        assert rec["resharding_copies"] == 0, (
            f"{rec['resharding_copies']} resharding copies "
            f"(epoch {rec['epoch']}): an input changed layout mid-run")
    post = recs[1:] or recs
    dt = recs[-1]["time_sec"] - recs[0]["time_sec"]
    frames = sum(r["anakin_frames"] for r in post)
    games = sum(r["anakin_games"] for r in post)
    steps = recs[-1]["steps"] - recs[0]["steps"]
    out = {
        "anakin_env_frames_per_sec": round(frames / dt, 1) if dt else None,
        "anakin_games_per_sec": round(games / dt, 1) if dt else None,
        "anakin_steps_per_sec_fused": round(steps / dt, 2) if dt else None,
        "fused_step_compiles": max(r["retrace_count"] for r in recs),
        "resharding_copies": sum(r["resharding_copies"] for r in recs),
    }

    # generation ceiling: the rollout alone, no update attached
    import jax
    import jax.numpy as jnp

    engine, model = _anakin_engine(num_envs=1024)
    roll = jax.jit(engine._rollout)
    params = jax.tree.map(jnp.array, model.params)
    batch, carry, frames_dev = roll(params, (), engine.init_carry(0))
    jax.block_until_ready(frames_dev)  # compile outside the window
    total, iters = 0, 6
    t0 = time.perf_counter()
    for _ in range(iters):
        batch, carry, frames_dev = roll(params, (), carry)
        total += int(frames_dev)
    ceiling_dt = time.perf_counter() - t0
    out["anakin_rollout_frames_per_sec"] = round(total / ceiling_dt, 1)
    print(json.dumps(out))
    sys.stdout.flush()
    os._exit(0)  # skip non-daemonic gather joins (intake_child idiom)


def anakin_host_child(epochs=3):
    """The comparator: the SAME real-Learner training fed by the host
    actor path (spawned workers, framed control plane, device replay).
    Emits fresh env frames/s delivered into the learner over the same
    steady-state window, plus the lockstep-pool microbenchmark (the
    host generation ceiling with no transport or learner contention)."""
    import shutil
    import tempfile
    import time as _time

    work = tempfile.mkdtemp(prefix="bench_anakin_host_")
    cwd = os.getcwd()
    os.chdir(work)
    try:
        args = {
            "env_args": {"env": "TicTacToe"},
            "train_args": {
                **ANAKIN_TRAIN_ARGS, "epochs": epochs,
                "updates_per_epoch": 40,
                "worker": {"num_parallel": 2},
            },
            "worker_args": {"num_parallel": 2, "server_address": ""},
        }
        from handyrl_tpu.learner import Learner

        learner = Learner(args)
        arrivals = []  # (learner-clock timestamp, env frames)
        orig_feed = learner.feed_episodes

        def feed(episodes):
            arrivals.append((
                _time.monotonic() - learner._run_t0,
                sum(e["steps"] for e in episodes if e)))
            orig_feed(episodes)

        learner.feed_episodes = feed
        learner.run()
        with open("metrics.jsonl") as f:
            recs = [json.loads(line) for line in f if line.strip()]
    finally:
        os.chdir(cwd)
        shutil.rmtree(work, ignore_errors=True)

    # the same steady-state window as the anakin child: first epoch
    # record (post worker bring-up + compile) to the last
    t_lo, t_hi = recs[0]["time_sec"], recs[-1]["time_sec"]
    frames = sum(n for t, n in arrivals if t_lo < t <= t_hi)
    dt = t_hi - t_lo
    out = {
        "host_env_frames_per_sec": round(frames / dt, 1) if dt else None,
    }
    cfg = dict(ANAKIN_TRAIN_ARGS, eval={"opponent": ["random"]})
    pool_sps, _ = _pool_throughput(
        "TicTacToe", cfg, k=16, target_episodes=400)
    out["host_pool_frames_per_sec"] = round(pool_sps, 1)
    print(json.dumps(out))
    sys.stdout.flush()
    os._exit(0)


def anakin_main(rounds=3, epochs=3):
    """Anakin variant (one JSON line, like main): fused on-device
    rollout+update vs the host actor path, as two REAL-Learner
    trainings on the same TicTacToe config — interleaved pairwise per
    round and ratioed within rounds, the `--pipeline`/`--durability`
    discipline (this host swings far more between trial blocks than
    either path's margin).

    Two ratios land in the JSON: the PATH ratio (fresh env frames/s
    trained by the fused loop vs delivered into the learner by the
    worker stack — the number the Anakin architecture exists to move,
    and the acceptance gate's >= 10x), and the generation-CEILING
    ratio (rollout-only jit vs the lockstep pool microbenchmark —
    both sides stripped of update/transport, the component view)."""
    runs = _interleaved_rounds(rounds, {
        "host": lambda: _run_child("--anakin-host-child", timeout=900,
                                   extra=[str(epochs)], on_chip=True),
        "fused": lambda: _run_child("--anakin-child", timeout=900,
                                    extra=[str(epochs)], on_chip=True),
        # GSPMD leg: the SAME fused training over a dp4 x tp2 mesh of
        # 8 virtual devices — sharded-vs-unsharded dispatch cost on
        # the fused step (on this CPU host the partition overhead is
        # the whole number; an accelerator mesh is where dp buys
        # throughput).  Hard-asserts the same 1-compile/0-reshard
        # contract as the single-device child
        "fused_mesh": lambda: _run_child(
            "--anakin-child", timeout=900, extra=[str(epochs), "mesh"],
            env_extra={"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=8"}),
    })
    anakin_fps, host_fps, ratios = [], [], []
    roll_fps, pool_fps = [], []
    extras = {}
    for host, fused in zip(runs["host"], runs["fused"]):
        if fused.get("anakin_env_frames_per_sec") \
                and host.get("host_env_frames_per_sec"):
            anakin_fps.append(fused["anakin_env_frames_per_sec"])
            host_fps.append(host["host_env_frames_per_sec"])
            ratios.append(fused["anakin_env_frames_per_sec"]
                          / host["host_env_frames_per_sec"])
            for k in ("anakin_games_per_sec",
                      "anakin_steps_per_sec_fused",
                      "fused_step_compiles", "resharding_copies"):
                if fused.get(k) is not None:
                    extras.setdefault(k, []).append(fused[k])
        if fused.get("anakin_rollout_frames_per_sec"):
            roll_fps.append(fused["anakin_rollout_frames_per_sec"])
        if host.get("host_pool_frames_per_sec"):
            pool_fps.append(host["host_pool_frames_per_sec"])
    if not ratios:
        print(json.dumps({"metric": "anakin_env_frames_speedup",
                          "error": "no complete rounds"}))
        return
    out = {
        "metric": "anakin_env_frames_speedup",
        "value": round(_median(ratios), 1),
        "unit": ("fused on-device env frames/s vs host-actor-path env "
                 "frames/s (TicTacToe, two real Learner runs per "
                 f"round, median of {len(ratios)} interleaved rounds; "
                 "gate >= 10)"),
        "anakin_env_frames_per_sec": _median(anakin_fps),
        "host_env_frames_per_sec": _median(host_fps),
        **{k: _median(v) for k, v in extras.items()},
        "rounds": {"anakin": anakin_fps, "host": host_fps,
                   "ratios": [round(r, 1) for r in ratios]},
    }
    if roll_fps and pool_fps:
        out["anakin_rollout_frames_per_sec"] = _median(roll_fps)
        out["host_pool_frames_per_sec"] = _median(pool_fps)
        out["generation_ceiling_ratio"] = round(
            _median(roll_fps) / _median(pool_fps), 1)
    mesh_ratios = _round_ratios(runs.get("fused_mesh", []),
                                runs["fused"],
                                key="anakin_env_frames_per_sec")
    mesh_runs = [r for r in runs.get("fused_mesh", [])
                 if r.get("anakin_env_frames_per_sec")]
    if mesh_runs:
        out["anakin_mesh_env_frames_per_sec"] = _median(
            [r["anakin_env_frames_per_sec"] for r in mesh_runs])
        out["mesh_resharding_copies"] = max(
            r.get("resharding_copies", 0) for r in mesh_runs)
        if mesh_ratios:
            # sharded/unsharded fused-step throughput within a round:
            # the dispatch-cost view of the dp4xtp2 mesh on this host
            out["mesh_vs_single_dispatch_ratio"] = round(
                _median(mesh_ratios), 3)
    print(json.dumps(out))


def measure_width_sweep(seed, widths=(32, 64, 128, 256),
                        batch_size=BATCH):
    """Steps/s + MFU vs GeeseNet width at the flagship batch: settles
    whether the low headline MFU is intrinsic to the 32-filter net
    (a 7x11 board can't fill a 128x128 MXU) or a framework defect.
    Measures each width's update step on device-resident batches."""
    import jax

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.models.geese_net import GeeseNet

    _, seed_batch, cfg = seed
    env = make_env({"env": "HungryGeese"})
    env.reset()
    obs0 = env.observation(env.players()[0])
    _, cells = batch_geometry(_tile(seed_batch, batch_size // SEED_EPS))
    kind = jax.devices()[0].device_kind

    sweep = {}
    for width in widths:
        model = TPUModel(GeeseNet(filters=width))
        model.init_params(obs0, seed=0)
        sps, _, step_ms = measure_learner(
            (model, seed_batch, cfg), batch_size, "bfloat16",
            iters=12, host_iters=0, timed_iters=5)
        flops_step = 3.0 * batch_size * cfg["forward_steps"] \
            * model_flops_per_sample(model.params, cells)
        # achieved-TFLOPs/MFU math shared with the runtime cost model
        perf = mfu_extras(flops_step, sps, kind=kind)
        entry = {
            "steps_per_sec": round(sps, 2),
            "step_time_ms_blocked": round(step_ms, 2),
            "tflops_est": perf["achieved_tflops_est"],
        }
        if "mfu_measured" in perf:
            entry["mfu"] = perf["mfu_measured"]
        sweep[str(width)] = entry
    return sweep


def setup_device_replay(seed, batch_size, compute_dtype, steps=40,
                        flood_mult=4):
    """Device-resident replay: episodes ingested into the HBM ring
    once (amortized), then every step draws indices, gathers the
    batch, and updates in ONE jit fed three host scalars (the
    production ``device_replay: auto`` learner path).

    Returns (trial, profile, ingest_eps): ``trial()`` times ``steps``
    fused update steps and may be called repeatedly (interleaved
    trials).  ``ingest_eps`` is the intake chain — ``offer()`` +
    ``ingest()`` draining ``flood_mult * len(episodes)`` pre-canned
    wire episodes through the consecutive-slot ``_append_run`` batched
    writes (decompress + pad + one device dispatch per 8 episodes),
    ring wraps included.  (Batched is the ONLY ingest path now — the
    legacy one-episode-per-dispatch rate it used to report measured a
    code path that no longer exists.)"""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer
    from handyrl_tpu.staging import DeviceReplay
    from handyrl_tpu.utils.profiling import SectionTimers

    model, _, cfg, episodes = seed
    rcfg = {
        "turn_based_training": cfg["turn_based_training"],
        "observation": cfg.get("observation", False),
        "forward_steps": cfg["forward_steps"],
        "burn_in_steps": cfg.get("burn_in_steps", 0),
        "transfer_dtype": "uint8",   # geese planes: binary
        "compute_dtype": compute_dtype,
    }
    replay = DeviceReplay(rcfg, capacity=len(episodes) + 2,
                          max_bytes=4 << 30)
    replay.offer(episodes)  # warm: sizes buffers, compiles the append
    replay.ingest(max_episodes=len(episodes))
    jax.block_until_ready(replay.buffers)

    # production intake on the warmed ring (append jit compiled, ring
    # at capacity so every write wraps like a steady-state run)
    flood = [episodes[i % len(episodes)]
             for i in range(flood_mult * len(episodes))]
    t0 = time.perf_counter()
    replay.offer(flood)
    while replay.pending:
        replay.ingest(max_episodes=64)
    jax.block_until_ready(replay.buffers)
    ingest_eps = len(flood) / (time.perf_counter() - t0)

    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    params = jax.tree.map(jnp.array, model.params)
    opt_state = optimizer.init(params)
    from handyrl_tpu.staging import make_replay_update_step, step_state

    # the production path: draw + gather + update fused into ONE jit
    # per step, fed three host scalars (no per-step array uploads).
    # seed fixed: deterministic draws keep bench runs comparable
    update = make_replay_update_step(
        replay, model, loss_cfg, optimizer, compute_dtype, batch_size,
        seed=0)

    timers = SectionTimers()
    state = {"params": params, "opt_state": opt_state,
             "draw": step_state(replay, 0)}

    def one_step(params, opt_state, draw):
        with timers.section("update"):
            return update(params, opt_state, replay.buffers, draw)

    params, opt_state, metrics, draw = one_step(
        params, opt_state, state["draw"])
    float(metrics["total"])  # compile + warmup sync
    state.update(params=params, opt_state=opt_state, draw=draw)
    timers.snapshot()  # drop the compile/warmup section

    def trial(n=steps):
        params, opt_state, draw = (
            state["params"], state["opt_state"], state["draw"])
        t0 = time.perf_counter()
        for _ in range(n):
            params, opt_state, metrics, draw = one_step(
                params, opt_state, draw)
        float(metrics["total"])  # sync
        sps = n / (time.perf_counter() - t0)
        state.update(params=params, opt_state=opt_state, draw=draw)
        return sps

    return (trial, lambda: {n: v["sec"]
                            for n, v in timers.snapshot().items()},
            ingest_eps)


# ---------------------------------------------------------------------
# actor benchmarks (CPU subprocess, like production workers)
# ---------------------------------------------------------------------

def _pool_throughput(env_name, cfg, k, target_episodes, seed=0):
    import random

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import RolloutPool
    from handyrl_tpu.models import TPUModel

    random.seed(seed)
    envs = [make_env({"env": env_name}) for _ in range(k)]
    envs[0].reset()
    model = TPUModel(envs[0].net())
    model.init_params(
        envs[0].observation(envs[0].players()[0]), seed=seed)
    pool = RolloutPool(envs, cfg)
    players = envs[0].players()
    job = {"role": "g", "player": players,
           "model_id": {p: 1 for p in players}}
    models = {p: model for p in players}
    while pool.has_free_slot():
        pool.assign(job, models)
    pool.step()  # compile

    done, steps = 0, 0
    t0 = time.perf_counter()
    while done < target_episodes:
        for verb, payload in pool.step():
            if payload is not None:
                done += 1
                steps += payload["steps"]
            if pool.has_free_slot():
                pool.assign(job, models)
    dt = time.perf_counter() - t0
    return steps / dt, steps * len(players) / dt


def actor_child():
    """CPU actor benchmark body (run in a subprocess, pinned to the
    CPU backend exactly like production workers: a child must choose
    the CPU before its first JAX call, the chip is the learner's)."""
    import random

    from handyrl_tpu.connection import force_cpu_jax

    force_cpu_jax()

    from __graft_entry__ import GEESE_CFG, TTT_CFG

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import Generator
    from handyrl_tpu.models import TPUModel

    cfg = dict(GEESE_CFG, eval={"opponent": ["random"]})
    geese_sps, geese_fps = _pool_throughput(
        "HungryGeese", cfg, k=16, target_episodes=40)

    ttt_cfg = dict(TTT_CFG, eval={"opponent": ["random"]})
    ttt_sps, _ = _pool_throughput(
        "TicTacToe", ttt_cfg, k=16, target_episodes=400)

    # sequential fallback (the r1/r2 shape: one batch-1 dispatch per
    # seat per step) for the speedup denominator
    random.seed(0)
    env = make_env({"env": "HungryGeese"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=0)
    gen = Generator(env, dict(GEESE_CFG))
    players = env.players()
    job = {"player": players, "model_id": {p: 1 for p in players}}
    models = {p: model for p in players}
    gen.generate(models, job)  # warmup
    steps, done = 0, 0
    t0 = time.perf_counter()
    while done < 2:
        ep = gen.generate(models, job)
        if ep is None:
            continue
        steps += ep["steps"]
        done += 1
    seq_dt = time.perf_counter() - t0
    n_players = len(players)

    print(json.dumps({
        "env_steps_per_sec": geese_sps,
        "env_frames_per_sec": geese_fps,
        "env_frames_per_sec_sequential": steps * n_players / seq_dt,
        "actor_env_steps_per_sec_ttt": ttt_sps,
    }))


def intake_child(num_parallel=32):
    """Episode-intake rate of the production gather tree:
    ``num_parallel`` actor processes x 8 lockstep episodes on
    TicTacToe, uniform-policy jobs (model_id 0), against a minimal
    in-process job server."""
    import queue

    from handyrl_tpu.connection import force_cpu_jax

    force_cpu_jax()

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel, RandomModel  # noqa: F401
    from handyrl_tpu.worker import WorkerCluster
    import pickle

    args = {
        "turn_based_training": True, "observation": False,
        "gamma": 0.8, "forward_steps": 8, "burn_in_steps": 0,
        "compress_steps": 4, "lambda": 0.7,
        "policy_target": "TD", "value_target": "TD",
        "seed": 0, "lockstep_episodes": 8,
        "eval": {"opponent": ["random"]},
        "env": {"env": "TicTacToe"},
        "worker": {"num_parallel": num_parallel},
    }
    env = make_env(args["env"])
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(0), seed=0)
    model_blob = pickle.dumps(model)
    players = env.players()
    job = {"role": "g", "player": players,
           "model_id": {p: 0 for p in players}}

    cluster = WorkerCluster(args)
    cluster.run()

    episodes = 0
    t_start = time.perf_counter()
    measure_from = None
    measured_eps = 0
    window = 20.0
    while True:
        now = time.perf_counter()
        if measure_from is not None and now - measure_from > window:
            break
        if now - t_start > 180:  # startup guard
            break
        try:
            conn, (verb, payload) = cluster.recv(timeout=0.3)
        except queue.Empty:
            continue
        batched = isinstance(payload, list)
        n = len(payload) if batched else 1
        if verb == "args":
            reply = [dict(job) for _ in range(n)]
        elif verb == "model":
            reply = [model_blob] * n
        else:
            if verb == "episode":
                episodes += n
                if (measure_from is None
                        and episodes >= max(16, 2 * num_parallel)
                        and now - t_start > 12.0):
                    # warmup done: all workers are up and generating
                    measure_from = now
                    measured_eps = episodes
            reply = [None] * n
        cluster.send(conn, reply if batched else reply[0])
    if measure_from is None:
        # warmup never completed: report the failure, not a made-up rate
        print(json.dumps({
            "intake_error": "warmup_timeout",
            "intake_episodes_seen": episodes,
            "intake_workers": num_parallel,
        }))
        sys.stdout.flush()
        os._exit(0)
    dt = time.perf_counter() - measure_from
    print(json.dumps({
        "intake_episodes_per_sec": (episodes - measured_eps) / dt,
        "intake_workers": num_parallel,
    }))
    sys.stdout.flush()
    os._exit(0)  # gathers exit on EOF; skip the non-daemonic joins


def _ceiling_flooder(conn, episode, block):
    """Pre-canned episode uploads as fast as the server will take them
    (the gather protocol: batched list + one ack per message)."""
    msg = ("episode", [episode] * block)
    try:
        while True:
            conn.send(msg)
            conn.recv()
    except (BrokenPipeError, EOFError, OSError):
        pass


def intake_ceiling_child(num_flooders=3, block=16, window=15.0):
    """Learner server-loop capacity with ZERO actor cost: flooder
    processes replay one pre-canned TicTacToe episode in gather-sized
    blocks; the parent drains them through the production
    QueueCommunicator.  Separates "actors are the intake limit" from
    "the server thread / pickle loop is the ceiling" (VERDICT r3 #7)."""
    import queue
    import random

    from handyrl_tpu.connection import (
        QueueCommunicator,
        force_cpu_jax,
        open_multiprocessing_connections,
    )

    force_cpu_jax()

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import Generator
    from handyrl_tpu.models import RandomModel, TPUModel

    random.seed(0)
    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    obs0 = env.observation(env.players()[0])
    model.init_params(obs0, seed=0)
    gen = Generator(env, {
        "turn_based_training": True, "observation": False,
        "gamma": 0.8, "compress_steps": 4,
    })
    players = env.players()
    job = {"player": players, "model_id": {p: 0 for p in players}}
    rollout = RandomModel(model, obs0)
    episode = None
    while episode is None:
        episode = gen.generate({p: rollout for p in players}, job)

    conns = open_multiprocessing_connections(
        num_flooders, _ceiling_flooder, lambda i: (episode, block))
    comm = QueueCommunicator(conns)

    count = 0
    t0 = time.perf_counter()
    measure_from = None
    measured = 0
    while True:
        now = time.perf_counter()
        if measure_from is not None and now - measure_from > window:
            break
        if now - t0 > 120:
            break
        try:
            conn, (verb, payload) = comm.recv(timeout=0.3)
        except queue.Empty:
            continue
        count += len(payload)
        comm.send(conn, [None] * len(payload))
        if measure_from is None and now - t0 > 3.0:
            measure_from = now
            measured = count
    dt = time.perf_counter() - measure_from if measure_from else 1.0
    print(json.dumps({
        "intake_ceiling_eps_per_sec": round((count - measured) / dt, 1),
        "intake_ceiling_flooders": num_flooders,
    }))
    sys.stdout.flush()
    os._exit(0)


def _interleaved_rounds(rounds, legs):
    """THE pairwise-round discipline shared by ``--durability`` /
    ``--pipeline`` / ``--anakin`` / ``--serve``: every leg callable
    runs once per round, interleaved in leg order, so cross-leg ratios
    can be computed WITHIN a round.  This host swings far more between
    trial blocks than most legs' margins — a blocked A-then-B
    comparison measures drift, not the margin (the 0.26 phantom "WAL
    overhead" that motivated the discipline).  Returns
    ``{leg_name: [per-round result, ...]}``."""
    from handyrl_tpu.analysis.guards import ResourceLedger

    ledger = ResourceLedger()
    out = {name: [] for name in legs}
    for i in range(rounds):
        base = ledger.sample()
        for name, run in legs.items():
            out[name].append(run())
        # one-line fd/thread/shm delta per round to stderr: a bench
        # round that leaks (a child's pipe end, a stranded shm ring)
        # compounds across rounds and skews every later leg's numbers
        print(f"round {i + 1}/{rounds} {ledger.delta_line(base)}",
              file=sys.stderr)
    return out


def _round_ratios(num, den, key=None):
    """Pairwise within-round ratios of two legs' result lists; dict
    results select ``key``.  Rounds where either side is missing or
    zero drop out (a failed child must not poison the median)."""
    ratios = []
    for a, b in zip(num, den):
        if key is not None:
            a = (a or {}).get(key)
            b = (b or {}).get(key)
        if a and b:
            ratios.append(a / b)
    return ratios


# every section or child that failed in this process: the report still
# prints what it has, and the process then exits non-zero (__main__)
_FAILED = []


def _run_child(flag, timeout=1200, extra=(), env_extra=None,
               on_chip=False):
    """One bench leg in a fresh interpreter.  ``on_chip`` legs are whole
    Learner trainings: they take whatever platform JAX finds (the chip,
    where there is one — this parent never touches JAX, so it is
    free).  Every other child — actors, intake, load generators, the
    virtual-device mesh legs — is CPU by design and pinned here."""
    env = dict(os.environ)
    if not on_chip:
        env["JAX_PLATFORMS"] = "cpu"
    if env_extra:
        env.update(env_extra)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), flag, *extra],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, cwd=os.path.dirname(os.path.abspath(__file__)),
        timeout=timeout,
    )
    if proc.returncode != 0:
        tail = "\n".join(proc.stderr.splitlines()[-5:])
        print(f"bench child {flag} failed (rc={proc.returncode}): {tail}",
              file=sys.stderr)
        _FAILED.append(f"child {flag} rc={proc.returncode}")
        return {f"child_error{flag.replace('-', '_')}": proc.returncode}
    for line in reversed(proc.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    return {}


def _median(xs):
    s = sorted(xs)
    return s[len(s) // 2]


N_TRIALS = 5


def main():
    import jax

    from __graft_entry__ import _build_model_and_batch

    seed4 = _build_model_and_batch(
        batch_size=SEED_EPS, return_episodes=True)
    seed = seed4[:3]
    model, seed_batch, cfg = seed

    solo_trial, sps_bf16_host, step_ms = setup_learner(
        seed, BATCH, "bfloat16")
    sps_f32, _, _ = measure_learner(seed, BATCH, "float32", iters=20,
                                    host_iters=0, timed_iters=0)
    sps64_bf16, _, _ = measure_learner(seed, R1_GEOMETRY_BATCH,
                                       "bfloat16", iters=20,
                                       host_iters=0, timed_iters=0)
    sps1024_bf16, _, _ = measure_learner(seed, 1024, "bfloat16",
                                         iters=15, host_iters=0,
                                         timed_iters=0)
    prefetch_sps = measure_prefetch(seed, BATCH, "bfloat16")
    try:
        dr_trial, dr_prof_fn, dr_ingest = \
            setup_device_replay(seed4, BATCH, "bfloat16")
    except Exception as exc:  # one broken section must not kill the report
        print(f"device-replay bench failed: {exc!r}", file=sys.stderr)
        _FAILED.append(f"device-replay section: {exc!r}")
        dr_trial, dr_ingest = None, None
        err = repr(exc)  # 'except ... as' unbinds at block exit
        dr_prof_fn = lambda: {"error": err}  # noqa: E731
    e2e_trial, e2e_stop, e2e_prof_fn = setup_pipeline(
        seed4, BATCH, "bfloat16", "uint8")

    # the three learner paths as INTERLEAVED trials in one process:
    # timings swing between processes, so cross-path ratios are
    # computed pairwise within each round and headline numbers are
    # medians over rounds, not single passes
    trials = {"solo": [], "device_replay": [], "e2e": []}
    for _ in range(N_TRIALS):
        trials["solo"].append(solo_trial())
        if dr_trial is not None:
            trials["device_replay"].append(dr_trial())
        trials["e2e"].append(e2e_trial())
        # let the prefetch queue refill before the next solo trial so
        # batcher work doesn't bleed into another section's window
        time.sleep(1.0)
    e2e_stop()
    dr_prof = dr_prof_fn()
    e2e_prof = e2e_prof_fn()

    sps_bf16 = _median(trials["solo"])
    e2e_sps = _median(trials["e2e"])
    dr_sps = (_median(trials["device_replay"])
              if trials["device_replay"] else None)

    baseline = {}
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE_MEASURED.json")) as f:
            baseline = json.load(f)
    except OSError:
        pass
    ref256 = baseline.get(f"learner_steps_per_sec_b{BATCH}")
    # headline = the PRODUCTION feed path (scalar-fed device replay);
    # solo is the device-resident ceiling, kept as an extra
    headline = dr_sps if dr_sps is not None else sps_bf16
    vs = headline / ref256 if ref256 else 1.0

    def stats(name):
        xs = trials[name]
        if not xs:
            return None
        return {"median": round(_median(xs), 2),
                "min": round(min(xs), 2), "max": round(max(xs), 2),
                "trials": [round(x, 2) for x in xs]}

    extras = {
        "learner_trials_b256": {k: stats(k) for k in trials},
        "learner_steps_per_sec_b256_solo": round(sps_bf16, 2),
        "learner_steps_per_sec_b256_f32": round(sps_f32, 2),
        "learner_steps_per_sec_b256_bf16_hostbatch": round(
            sps_bf16_host, 2),
        "learner_steps_per_sec_b256_prefetch": round(prefetch_sps, 2),
        "learner_steps_per_sec_b256_e2e": round(e2e_sps, 2),
        "e2e_batch_wait_sec": e2e_prof.get("batch_wait"),
        "e2e_update_sec": e2e_prof.get("update"),
        "learner_steps_per_sec_b256_device_replay":
            round(dr_sps, 2) if dr_sps is not None else None,
        # the draw is fused in-jit since late r4: no sample section
        "device_replay_update_sec": dr_prof.get("update"),
        # the batched offer()+ingest() chain — the ONLY ingest path
        # (the legacy per-episode dispatch it was once compared
        # against is deleted)
        "device_replay_ingest_eps_per_sec":
            round(dr_ingest, 1) if dr_ingest is not None else None,
        "learner_steps_per_sec_b64_bf16": round(sps64_bf16, 2),
        "learner_steps_per_sec_b1024_bf16": round(sps1024_bf16, 2),
        "reference_steps_per_sec_b256_torch_cpu": ref256,
        "reference_steps_per_sec_b64_torch_cpu":
            baseline.get("learner_steps_per_sec"),
    }
    if trials["device_replay"]:
        extras["device_replay_vs_solo_median"] = round(_median(
            [r / s for r, s in zip(trials["device_replay"],
                                   trials["solo"])]), 3)
        extras["e2e_vs_device_replay_median"] = round(_median(
            [e / r for e, r in zip(trials["e2e"],
                                   trials["device_replay"])]), 3)

    samples, cells = batch_geometry(
        _tile(seed_batch, BATCH // SEED_EPS))
    # fwd + bwd ~= 3x forward FLOPs
    flops_step = 3.0 * samples * model_flops_per_sample(
        model.params, cells)
    extras["flops_per_step_est"] = flops_step
    extras["samples_per_step"] = samples
    # pipelined time is the real sustained per-step cost; the blocked
    # time additionally pays one full host<->device sync per step
    extras["step_time_ms_pipelined"] = round(1e3 / sps_bf16, 3)
    extras["step_time_ms_blocked_incl_sync"] = round(step_ms, 3)
    kind = jax.devices()[0].device_kind
    extras["device_kind"] = kind
    # achieved-TFLOPs/MFU math shared with the runtime cost model
    extras.update(mfu_extras(flops_step, sps_bf16, kind=kind))

    # MFU vs model width: VERDICT r3 asked whether the low headline MFU
    # is intrinsic to the 32-filter flagship net — sweep and see
    try:
        extras["width_sweep_b256"] = measure_width_sweep(seed)
    except Exception as exc:
        print(f"width sweep failed: {exc!r}", file=sys.stderr)
        _FAILED.append(f"width sweep: {exc!r}")
        extras["width_sweep_b256"] = {"error": repr(exc)}

    extras.update(_run_child("--actor-child"))
    # gather-tree scaling over the actor-process count
    intake_scaling = {}
    for n in (4, 16, 32):
        result = _run_child("--intake-child", timeout=600, extra=[str(n)])
        if "intake_episodes_per_sec" in result:
            intake_scaling[str(n)] = round(
                result["intake_episodes_per_sec"], 1)
            if n == 32:
                extras.update(result)  # the headline intake record
        elif result:
            extras[f"intake_error_w{n}"] = result.get(
                "intake_error", "child_failed")
    extras["intake_scaling_by_workers"] = intake_scaling
    # server-loop capacity with zero actor cost: names the bottleneck
    extras.update(_run_child("--intake-ceiling-child", timeout=300))
    ceiling = extras.get("intake_ceiling_eps_per_sec")
    measured = extras.get("intake_episodes_per_sec")
    if ceiling and measured:
        extras["intake_bottleneck"] = (
            "actors (server has headroom)" if ceiling > 2 * measured
            else "server loop")
    ref_actor = baseline.get("actor_env_steps_per_sec_ttt")
    if ref_actor and extras.get("actor_env_steps_per_sec_ttt"):
        extras["reference_actor_env_steps_per_sec_ttt"] = ref_actor
        extras["actor_vs_reference_ttt"] = round(
            extras["actor_env_steps_per_sec_ttt"] / ref_actor, 2)
    for key in ("env_frames_per_sec", "env_steps_per_sec",
                "env_frames_per_sec_sequential",
                "actor_env_steps_per_sec_ttt",
                "intake_episodes_per_sec"):
        if isinstance(extras.get(key), float):
            extras[key] = round(extras[key], 1)

    path_name = ("scalar-fed device-replay fused step"
                 if dr_sps is not None
                 else "device-resident solo step (replay section "
                      "failed)")
    print(json.dumps({
        "metric": "learner_update_steps_per_sec",
        "value": round(headline, 2),
        "unit": (f"steps/sec (GeeseNet bf16, {path_name}, "
                 f"batch={BATCH}x{cfg['forward_steps']}x1p,"
                 f" median of {N_TRIALS} interleaved trials)"),
        "vs_baseline": round(vs, 3),
        **extras,
    }))


if __name__ == "__main__":
    from handyrl_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()   # before any compile; children inherit it
    if "--actor-child" in sys.argv:
        actor_child()
    elif "--intake-child" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        intake_child(int(tail[0]) if tail else 32)
    elif "--intake-ceiling-child" in sys.argv:
        intake_ceiling_child()
    elif "--lag-tolerance" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        lag_tolerance_main(steps=int(tail[0]) if tail else 12)
    elif "--durability" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        durability_main(steps=int(tail[0]) if tail else 12)
    elif "--pipeline-child" in sys.argv:
        tail = sys.argv[sys.argv.index("--pipeline-child") + 1:]
        mode = tail[0] if tail else "on"
        pipeline_train_child(
            mode, epochs=int(tail[1]) if len(tail) > 1 else 3)
    elif "--pipeline" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        pipeline_main(rounds=int(tail[0]) if tail else 3)
    elif "--serve-child" in sys.argv:
        tail = sys.argv[sys.argv.index("--serve-child") + 1:]
        serve_child(tail[0] if tail else "batched")
    elif "--serve" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        serve_main(rounds=int(tail[0]) if tail else 2)
    elif "--router-child" in sys.argv:
        tail = sys.argv[sys.argv.index("--router-child") + 1:]
        router_child(tail[0] if tail else "pool")
    elif "--router" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        router_main(rounds=int(tail[0]) if tail else 2)
    elif "--anakin-child" in sys.argv:
        tail = sys.argv[sys.argv.index("--anakin-child") + 1:]
        digits = [a for a in tail if a.isdigit()]
        anakin_train_child(epochs=int(digits[0]) if digits else 3,
                           mesh="mesh" in tail)
    elif "--anakin-host-child" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        anakin_host_child(epochs=int(tail[0]) if tail else 3)
    elif "--anakin" in sys.argv:
        tail = [a for a in sys.argv[2:] if a.isdigit()]
        anakin_main(rounds=int(tail[0]) if tail else 3)
    else:
        main()
    if _FAILED:
        print(f"bench: {len(_FAILED)} section(s)/child(ren) failed: "
              f"{_FAILED}", file=sys.stderr)
        sys.exit(1)
