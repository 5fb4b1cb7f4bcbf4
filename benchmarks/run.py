"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time: refuses to run without the cell's TPU chips
(never the CPU), builds the program's ``Learner`` from the cell's
configuration as ``train_main`` does, sets it up by counted steps (each
printed as ``setup_phase <name> <seconds>``), measures for ``--seconds``
and prints ONE JSON object as its last line of standard output.

What belongs to a cell is data: ``BENCHMARK.json`` names the
configuration (``benchmarks/configs/``), the traffic mix
(``benchmarks/traffic/``) and the per-layer readers
(``benchmarks/layer_metrics/``); no code here reads a cell's name.
Beside its sizes, its limits and the name of its plain reference's net
(``reference``: ``benchmarks/reference/``) a configuration may state
the reference's training side (``reference_training``: a module there
too, see its ``__init__.py``), who plays its corpus (``corpus.policy``:
``harness/corpus.py``), the count of its fused step (``cost``: a module
of ``benchmarks/cost/``, see ``harness/roofline.py``), which layers
hold stacked kernels or the trunk's projections (``stacked_layers``,
``trunk_layers``: ``harness/weights.py``) and the rate it trains at
(``train_args.base_lr``: ``harness/rate.py``, read by the program's
side and the reference's alike); absent, each is what the harness did
before the key existed.

After the window, in this order: the ring's rows and the counts are
checked, a traced run takes the step's phases, every array on the
device is freed, and only then the plain reference follows the first
steps: it has the chip to itself.
"""

import argparse
import importlib.util
import json
import os
import shutil
import sys
import threading
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_EPOCHS = 1         # a traced run records this many WHOLE epochs of its
#                          mix (update_episodes / rate_eps seconds each), after
#                          its window: boundaries come once an epoch, so the
#                          stretch holds one boundary's stall wherever it starts
SETTLE_SECONDS = 10.0    # after the window: let queued episodes land


def _since_process_start():
    """Seconds since the interpreter was started (not since this file
    was reached)."""
    import psutil

    return time.time() - psutil.Process(os.getpid()).create_time()


class Run:
    """The finished run, as the per-layer readers see it: plain
    attributes set once in ``main``."""


def _say(line):
    print(line, flush=True)


class Phases:
    """Set-up as named, timed phases.  Each prints
    ``setup_phase <name> <seconds>`` at the start of a line (the program
    prints progress marks without a newline), followed by what JAX
    compiled or fetched from its persistent cache meanwhile (a phase
    that swings from run to run names its cause there) and the most
    host memory the process has held so far."""

    def __init__(self):
        self.last = -_since_process_start()
        self.origin = time.perf_counter()
        self.compiles = {"programs": 0, "seconds": 0.0, "hits": 0}

    def listen(self, jax):
        def duration(name, seconds, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles["programs"] += 1
                self.compiles["seconds"] += seconds

        def event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.compiles["hits"] += 1

        jax.monitoring.register_event_duration_secs_listener(duration)
        jax.monitoring.register_event_listener(event)

    def mark(self, name):
        now = time.perf_counter() - self.origin
        c, self.compiles = self.compiles, {
            "programs": 0, "seconds": 0.0, "hits": 0}
        _say(f"\nsetup_phase {name} {now - self.last:.3f}  "
             f"(programs built {c['programs']} in {c['seconds']:.2f} s, "
             f"{c['hits']} from the persistent cache; host peak rss "
             f"{_host_peak_rss() / 1e9:.2f} GB)")
        self.last = now


def _wait(condition, what, timeout, failed=lambda: None, grain=0.005):
    """Wait for a COUNT to be reached (never for a time to pass); the
    grain only bounds how late the count is noticed."""
    deadline = time.perf_counter() + timeout
    while not condition():
        problem = failed()
        if problem is not None:
            raise RuntimeError(f"while waiting for {what}: {problem!r}")
        if time.perf_counter() > deadline:
            raise RuntimeError(f"timed out after {timeout}s waiting for {what}")
        time.sleep(grain)


def _log_compiles():
    """(time, message) of every program JAX says it compiles: the names
    behind ``window_compiles`` when it is not 0."""
    import logging

    seen = []

    class Keep(logging.Handler):
        def emit(self, record):
            message = record.getMessage()
            if message.startswith("Compiling "):
                seen.append((time.perf_counter(), message[:100]))

    logger = logging.getLogger("jax._src.interpreters.pxla")
    logger.addHandler(Keep(level=logging.DEBUG))
    logger.setLevel(logging.DEBUG)
    logger.propagate = False
    return seen


def _reader(name):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def main(argv=None, rehearsal=None):
    """``rehearsal`` is for the benchmark's own tests only (a dict of
    tiny-size overrides, run on whatever backend JAX finds): the command
    line cannot set it, so a measured run never skips the chip check."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = parser.parse_args(argv)

    from benchmarks.harness.cells import Cell, load_manifest

    cell = Cell(load_manifest(), opts.workload)
    if cell.traffic["kind"] != "fed":
        raise SystemExit(
            f"traffic {cell.traffic_name!r} is of kind "
            f"{cell.traffic['kind']!r}; the generator knows 'fed'")
    phases = Phases()

    # the program's own cache rule (main.py calls the same helper):
    # JAX_COMPILATION_CACHE_DIR where the machine sets it, else
    # <checkout>/.jax_cache
    from handyrl_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    # keep EVERY program in the persistent cache, the small ones too
    # (JAX's default leaves out what compiles in under a second, and a
    # set-up builds dozens of those)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    import jax

    phases.listen(jax)
    phases.mark("import")
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if rehearsal is None and (device["platform"] != "tpu"
                              or device["count"] != cell.chips):
        print(f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{device}; nothing was run", file=sys.stderr)
        return 2
    phases.mark("backend")
    _say(f"compile cache: {cache_dir}")

    # -- corpus -------------------------------------------------------
    from benchmarks.harness import check, corpus as corpus_mod
    from benchmarks.harness import feed, priming, rate, roofline, weights
    from benchmarks.harness import trace as trace_mod
    from benchmarks.harness.probes import Probes

    config = cell.config
    if rehearsal:
        config = json.loads(json.dumps(config))
        config["train_args"].update(rehearsal.get("train_args", {}))
        config["corpus"].update(rehearsal.get("corpus", {}))
        cell.config = config
        cell.traffic.update(rehearsal.get("traffic", {}))
    corpus = corpus_mod.load_corpus(cell.config_name, config)
    phases.mark("corpus")

    # -- build --------------------------------------------------------
    from handyrl_tpu import staging
    from handyrl_tpu.environment import make_env, prepare_env
    from handyrl_tpu.learner import Learner
    from handyrl_tpu.models.wrapper import TPUModel

    run_dir = os.path.join(BENCH_DIR, ".cache", "run", cell.name)
    shutil.rmtree(run_dir, ignore_errors=True)   # a fresh run, no resume
    os.makedirs(run_dir)
    os.chdir(run_dir)        # models/, WAL and metrics resolve on the CWD
    args = cell.program_args()
    train = args["train_args"]
    prepare_env(args["env_args"])
    env = make_env(args["env_args"])
    env.reset()
    model = TPUModel(env.net())
    shapes = weights.param_shapes(
        model.module, env.observation(env.players()[0]),
        model.init_hidden([1]))
    model.params = weights.config_params(shapes, opts.seed, config)
    initial_params = jax.device_get(model.params)
    learner = Learner(args=rate.program_args(args), net=model)
    trainer, replay = learner.trainer, learner.trainer.device_replay
    rate.state(trainer, train)
    if replay is None or trainer._replay_step is None:
        raise RuntimeError("the learner built no device replay ring: "
                           "this harness times the fused replay step")
    # an open-loop cell starts no actors: the Learner is built whole,
    # and only its fleet's spawn is left out
    learner.worker.run = lambda: None
    phases.mark("build")

    # -- prime: the ring to its final shape, once ---------------------
    minimum = train["minimum_episodes"]
    t_max = -(-int(config["horizon_steps"]) // staging._GROW_ROUND) \
        * staging._GROW_ROUND
    groups, rest = priming.prime_groups(
        staging, corpus, minimum, t_max, opts.seed)
    primed = []
    for group in groups:
        replay.offer(list(group))
        replay.ingest(max_episodes=len(group), batch=len(group))
        primed += group
    replay.warm_start(rest)
    primed += rest
    if (replay.size != minimum or replay.t_max != t_max
            or replay.growths or replay.pending):
        raise RuntimeError(
            f"priming left the ring at size {replay.size} (wanted "
            f"{minimum}), t_max {replay.t_max} (wanted {t_max}), "
            f"growths {replay.growths}, pending {len(replay.pending)}")
    ring = {"capacity": replay.capacity, "t_max": replay.t_max,
            "row_bytes": sum(int(leaf.shape[1]) * leaf.dtype.itemsize
                             for leaf in jax.tree.leaves(
                                 {k: v for k, v in replay.buffers.items()
                                  if k not in staging._PER_SLOT}))}
    phases.mark("prime")

    # -- compile: the fused step's first calls ------------------------
    if rehearsal and rehearsal.get("wrap_step"):
        # a test breaks the timed path here, underneath the harness
        trainer._replay_step = rehearsal["wrap_step"](trainer._replay_step)
    probes = Probes(learner, annotate=bool(opts.trace))
    probes.install()
    lowered = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_kw: lowered.append(time.perf_counter())
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration"
        else None)
    compiled_names = _log_compiles()
    outcome = {}

    def drive():
        try:
            learner.run()
        except BaseException as exc:   # reported below; the run fails
            outcome["failure"] = exc

    runner = threading.Thread(target=drive, name="bench-learner")
    runner.start()

    def failed():
        return outcome.get("failure") or trainer.failure

    try:
        _wait(probes.captured_done.is_set,
              "the first three fused steps", 1500, failed)
        phases.mark("compile")

        # -- warm: a fixed count of offers and of steps ---------------
        order = feed.offer_order(len(corpus), opts.seed)
        feeder = feed.Feeder(learner, probes, corpus, order,
                             cell.traffic["rate_eps"])
        feeder.start()
        warm = int(cell.traffic["warm_offers"])
        _wait(lambda: len(probes.pairing.landed) >= warm,
              f"{warm} warm offers in the ring", 600,
              lambda: failed() or feeder.failure)
        _wait(lambda: trainer.steps >= int(cell.traffic["warm_steps"]),
              "the warm steps", 600, failed)
        # a window edge is closed on the trainer thread at a step
        # boundary, with the device caught up
        t_open, steps_open = probes.request_edge()
        phases.mark("warm")
        setup_s = _since_process_start() - (time.perf_counter() - t_open)
        counts_open = {"received": learner.episodes_received,
                       "filled": replay.size, "dropped": replay.dropped,
                       "rejected": learner.episodes_rejected_stale}

        # -- the window -----------------------------------------------
        time.sleep(max(0.0, opts.seconds - (time.perf_counter() - t_open)))
        t_close, steps_close = probes.request_edge()
        counts_close = {"received": learner.episodes_received,
                        "filled": replay.size, "dropped": replay.dropped,
                        "rejected": learner.episodes_rejected_stale}
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        if opts.trace:
            # the traced stretch FOLLOWS the window, under the same
            # load: stopping the profiler takes many seconds of host
            # work, which must not fall into what the window measured
            trace_dir = os.path.join(BENCH_DIR, ".cache", "trace", cell.name)
            shutil.rmtree(trace_dir, ignore_errors=True)
            # no Python tracer (it slows the host severalfold: the trainer
            # thread then starves the device, idle read 78-89%) and no HLO
            # proto (with it on, the DRC step's while loops ran 1.7x slower
            # on the device: 15.9 ms a step where 9.2 is right); my chip
            # runs, PR 24
            profile = jax.profiler.ProfileOptions()
            profile.python_tracer_level = 0
            profile.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=profile)
            with jax.profiler.TraceAnnotation("bench:window"):
                time.sleep(TRACE_EPOCHS * train["update_episodes"]
                           / float(cell.traffic["rate_eps"]))
            # offers end with the traced stretch: at 50 episodes/s the
            # ~25 s that stopping the profiler takes would wrap the
            # ring over the window's own episodes
            feeder.stop()
            jax.profiler.stop_trace()
        growths = replay.growths
        feeder.stop()
        # outside the window: let what was offered in it land, so
        # every episode of the window has a true time to the ring
        settle = time.perf_counter() + SETTLE_SECONDS
        while (len(probes.pairing.landed) + len(probes.pairing.shed)
               < len(feeder.offers)
               and time.perf_counter() < settle and failed() is None):
            time.sleep(0.01)
    finally:
        # -- teardown: the program's own, through Learner.run ---------
        children = []
        try:
            import psutil

            children = psutil.Process().children(recursive=True)
        except Exception:
            pass
        learner.shutdown_flag = True
        for child in children:
            try:
                child.terminate()
            except Exception:
                pass
        runner.join(timeout=120)
        if children:
            import psutil

            _, alive = psutil.wait_procs(children, timeout=20)
            for child in alive:
                child.kill()
            psutil.wait_procs(alive, timeout=20)

    if outcome.get("failure") is not None or runner.is_alive():
        raise RuntimeError(
            f"the learner did not end cleanly: {outcome.get('failure')!r}")

    # -- the run, as the readers see it -------------------------------
    run = Run()
    run.notes = {}
    run.cell, run.chips, run.probes, run.feeder = cell, cell.chips, probes, feeder
    run.t_open, run.t_close = t_open, t_close
    run.window_s = t_close - t_open
    if opts.trace:
        traced = trace_mod.reduce(
            trace_mod.load(trace_mod.find_xplane(trace_dir)),
            uncovered="idle_at_cap" if trainer.updates_cap else "untracked")
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        traced = None
    run.trace = traced
    run.window_compiles = sum(1 for t in lowered if t_open <= t < t_close)
    run.device = dict(device, memory_peak_bytes=int(peak))
    run.step_cost = roofline.cost_function(config)(
        shapes, train, config["roofline"], ring["row_bytes"])
    in_window = [(due, at) for due, at in probes.pairing.landed
                 if t_open <= due < t_close]
    run.episode_waits = lambda: [at - due for due, at in in_window]

    steps = steps_close - steps_open
    received = counts_close["received"] - counts_open["received"]
    shed = (counts_close["dropped"] - counts_open["dropped"]
            + counts_close["rejected"] - counts_open["rejected"])
    attempted = sum(1 for due, _ in feeder.offers
                    if t_open <= due < t_close)
    late_shed = sum(1 for due in probes.pairing.shed
                    if t_open <= due < t_close)
    never = attempted - len(in_window) - late_shed
    failed_count = late_shed + max(never, 0)

    values = {
        "setup_s": setup_s,
        "learner_frames_per_s": steps * train["batch_size"]
        * train["forward_steps"] / run.window_s,
    }
    waits = run.episode_waits()
    if waits:
        from benchmarks.harness.layers import percentile

        # the typical episode's wait is end to end where a cell lists
        # it; the tail is a per-layer metric (episode_to_ring_p95_ms,
        # ingest_wait_p95_ms) over the same pairs, printed in every run
        values["episode_to_ring_p50_ms"] = 1e3 * percentile(waits, 50)
        _say(f"episode_to_ring: {len(waits)} episodes, mean "
             f"{1e3 * sum(waits) / len(waits):.3f} ms, median "
             f"{1e3 * percentile(waits, 50):.3f} ms, p75 "
             f"{1e3 * percentile(waits, 75):.3f} ms, p90 "
             f"{1e3 * percentile(waits, 90):.3f} ms, p95 "
             f"{1e3 * percentile(waits, 95):.3f} ms")
    if probes.edge_blocks:
        _say("edges waited for the device: "
             + ", ".join(f"{1e3 * b:.1f} ms" for b in probes.edge_blocks))
    _say(f"ring: {ring['capacity']} slots of {ring['t_max']} rows; "
         f"{counts_open['filled']} hold an episode at the window's "
         f"opening edge, {counts_close['filled']} at its close")
    _say(f"window: {run.window_s:.4f} s, {steps} fused steps, "
         f"{received} episodes received, {shed} shed or rejected, "
         f"ring growths {growths}, programs lowered in the window "
         f"{run.window_compiles}")

    # -- correct: outside the window and outside setup_s --------------
    # What needs the program's live state comes first: the ring's rows,
    # the counts and, in a traced run, the step's phases (the trainer
    # caches its answer; the readers below read the cache).  Then every
    # array the process holds on the device is freed, so that the plain
    # reference has the chip to itself: beside a train state and ring
    # of gigabytes its own float32 parameters, gradient and Adam would
    # not fit.  The peak was read at the window's close, before any of
    # this; ``probes.captured`` and ``initial_params`` are host copies.
    t_check = time.perf_counter()
    ring_numbers = {
        "ring_mismatch": _check_ring_rows(
            config, train, opts.seed, replay, probes, corpus, feeder, ring,
            t_open, t_close),
        "unaccounted": float(abs(
            learner.episodes_received - learner.episodes_rejected_stale
            - (replay.episodes_seen - len(primed)) - replay.dropped
            - len(replay.pending)))}
    if opts.trace:
        t_profile = time.perf_counter()
        trainer.step_profile()
        _say(f"step profile took {time.perf_counter() - t_profile:.2f} s")
    deleted = _release_device()
    in_use = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                 for d in devices)
    _say(f"device bytes in use before the reference: {in_use} "
         f"({deleted} arrays deleted)")
    import numpy as np

    rss_before = _host_peak_rss()
    reference = check.reference_follow(
        config, train, primed, ring["capacity"], initial_params,
        steps=len(probes.captured["losses"]))
    _say(f"check losses program {probes.captured['losses']} "
         f"reference {reference[0]}")
    numbers = check.training_numbers(
        probes.captured, reference, initial_params)
    # how far each side moved over the three steps, compared with no
    # limit: a rate read on one side only would show here first
    moved = {side: float(np.median(check.leaf_norms(
        final, minus=initial_params))) for side, final in (
            ("program", probes.captured["params_after_third"]),
            ("reference", reference[2]))}
    _say("check change of the median leaf over the three steps: "
         f"program {moved['program']:.6g} reference "
         f"{moved['reference']:.6g} at a stated rate of "
         f"{train.get(rate.KEY, 'none')} a frame")
    numbers.update(ring_numbers)
    correct, lines = check.verdict(numbers, config["check_limits"])
    for line in lines:
        _say(line)
    _say(f"check took {time.perf_counter() - t_check:.2f} s")
    _say(f"host peak rss: {_host_peak_rss()} bytes "
         f"({rss_before} before the reference)")
    for t, message in compiled_names:
        if t_open <= t < t_close:
            _say(f"compiled in the window: {message}")
    if growths or run.window_compiles:
        raise RuntimeError(
            f"set-up did not hold: ring growths {growths}, programs "
            f"lowered in the window {run.window_compiles}")

    if opts.trace:
        metrics = {}
        for metric in cell.per_layer:
            value = _reader(metric["name"])(run)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}
        run.device.update(busy_s=traced["busy_s"],
                          window_s=traced["window_s"])
    else:
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    for key, value in run.notes.items():
        _say(f"note {key} {value}")
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed_count), "metrics": metrics,
              "device": run.device, "median_leaf_change": moved}
    if traced is not None:
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    # every number compared, beside its limit: last in the line, and
    # the last lines of standard error
    limits = config["check_limits"]
    result["check"] = {name: {"value": float(value),
                              "limit": float(limits[name])}
                       for name, value in numbers.items()}
    os.chdir(BENCH_DIR)
    shutil.rmtree(run_dir, ignore_errors=True)   # WAL and checkpoints
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def _host_peak_rss():
    """The most host memory this process has held, in bytes (Linux
    counts ``ru_maxrss`` in KiB): what the next configuration's train
    state and the check's host copies are sized against."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _release_device():
    """Delete every array this process still holds on the device (the
    learner has ended: train state, ring, snapshots, the constants its
    programs closed over); returns how many.  Nothing of the program is
    called after this but ``step_profile``'s cached answer and host
    counters."""
    import jax

    arrays = jax.live_arrays()
    for array in arrays:
        array.delete()
    return len(arrays)


def _check_ring_rows(config, train, seed, replay, probes, corpus, feeder,
                     ring, t_open, t_close, sample=64):
    """A seeded sample of windows from episodes appended DURING the
    window, fetched back from the ring by the program's gather, against
    the episodes themselves (the harness holds the episodes it
    offered); returns the count of rows that differ."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.harness import check

    training, _net, one_seat = check.reference_setup(config, train)
    # the k-th landed episode is the k-th offered one not shed
    shed = set(probes.pairing.shed)
    offered = [i for (due, _), i in zip(
        feeder.offers, (feeder.order[k % len(feeder.order)]
                        for k in range(len(feeder.offers))))
        if due not in shed]
    slot_of = []
    for first, lengths in probes.appends:
        slot_of += [(first + k) % ring["capacity"]
                    for k in range(len(lengths))]
    # each slot's LAST writer; only slots still held by an episode of
    # the window are sampled (a later append may have taken the slot)
    owner = {}
    for (due, _at), index, slot in zip(
            probes.pairing.landed, offered, slot_of):
        owner[slot] = (index, t_open <= due < t_close)
    recent = {slot: index for slot, (index, mine) in owner.items() if mine}
    if not recent:
        return float(sample)
    rng = np.random.default_rng(seed)
    slots = rng.choice(sorted(recent), size=sample)
    columns = {int(s): training.episode_columns(corpus[recent[int(s)]])
               for s in set(slots.tolist())}
    starts = np.asarray([rng.integers(0, 1 + max(
        0, columns[int(s)]["length"] - train["forward_steps"]))
        for s in slots], np.int32)
    seats = rng.integers(0, columns[int(slots[0])]["prob"].shape[1],
                         size=sample).astype(np.int32) if one_seat \
        else np.zeros(sample, np.int32)
    want = training.gather(columns, slots.tolist(), starts, seats,
                           train["forward_steps"], train["burn_in_steps"],
                           one_seat)
    got = jax.device_get(replay._sample_fn(
        replay.buffers, jnp.asarray(slots.astype(np.int32)),
        jnp.asarray(starts), jnp.asarray(seats)))
    bad = np.zeros(sample, bool)
    for key in want:
        for w, g in zip(jax.tree.leaves(want[key]),
                        jax.tree.leaves(got[key])):
            g = np.asarray(g, np.float32).reshape(sample, -1)
            w = np.asarray(w, np.float32).reshape(sample, -1)
            if key == "progress":
                # step / length, divided on the device: the chip's
                # division is not the host's to the last bit
                bad |= (np.abs(g - w) > 1e-6).any(axis=1)
            else:       # stored as written: exact
                bad |= (g != w).any(axis=1)
    return float(bad.sum())


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the program's daemon threads may still hold the runtime: leave
    # without waiting on them
    os._exit(code)
