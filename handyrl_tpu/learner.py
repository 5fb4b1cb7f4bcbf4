"""The learner: conductor server, training thread, batcher farm.

Role parity with /root/reference/handyrl/train.py:270-644, re-designed
TPU-first:

  * the Trainer's per-batch Python work is ONE jitted ``update_step``
    (grad + clip + Adam fused into a single XLA program); params and
    optimizer state live on device the whole epoch and are donated
    across steps — the host only touches them to snapshot an epoch;
  * under a device mesh the same step runs SPMD with the batch sharded
    over ``dp`` and XLA all-reducing gradients over ICI
    (handyrl_tpu.parallel) — replacing ``nn.DataParallel``;
  * batch assembly stays on CPU in batcher processes; finished batches
    stream through a device prefetch so H2D copy overlaps compute;
  * metrics accumulate on device and sync once per epoch, keeping the
    hot loop free of host round trips;
  * the epoch lr anneal (3e-8 * data_count_ema / (1 + steps*1e-5),
    reference train.py:383-385) pokes an injected optax hyperparameter
    — no recompile.

The stdout log format (``updated model(N)``, ``epoch N``, ``win rate``,
``loss = ...``, ``generation stats``) matches the reference exactly:
the plot scripts parse these prefixes, so the format is a public API
(/root/reference/scripts/win_rate_plot.py:33-51).
"""

import functools
import json
import os
import pickle
import queue
import random
import threading
import time
from collections import deque
from contextlib import contextmanager

import jax
import numpy as np
import psutil

from . import telemetry
from .analysis.guards import (
    HostTransferGuard,
    LockOrderGuard,
    NumericsGuard,
    ResourceLedger,
    RetraceGuard,
    ShardingContractGuard,
    StallWatchdog,
)
from .batch import make_batch
from .connection import MultiProcessJobExecutor
from .durability import (
    CheckpointManifest,
    CorruptCheckpointError,
    EpisodeWAL,
    read_verified,
    resolve_restart,
    write_checksummed,
)
from .environment import make_env, prepare_env
from .models import TPUModel, snapshot_params
from .resilience import FleetRegistry
from .parallel.mesh import OrderedLaunch
from .utils.compile_cache import metadata_in_key
from .utils.profiling import SectionTimers, TraceWindow
from .ops.losses import SEQUENCE_COUNTERS, LossConfig
from .ops.targets import noting as noting_targets
from .ops.update import (
    DEFAULT_LR,
    make_optimizer,
    make_update_step,
    set_learning_rate,
)
from .worker import WorkerCluster, WorkerServer


def _models_dir():
    return "models"


def model_path(model_id):
    return os.path.join(_models_dir(), f"{model_id}.ckpt")


def latest_model_path():
    return os.path.join(_models_dir(), "latest.ckpt")


def train_state_path():
    return os.path.join(_models_dir(), "train_state.ckpt")


def write_atomic(path, state, checksum=True):
    """Pickle to tmp + fsync + rename so a crash mid-write can never
    corrupt a file a restart (or a worker fetching a snapshot) will
    read — and, with ``checksum`` on (``checkpoint_checksum``), stamp
    a sha256 footer so a restart can PROVE the bytes it found are the
    bytes that were written (durability.read_verified rejects
    truncation and bit rot; the footer trails the pickle stream, so
    legacy readers still load the file).  Returns the content digest
    ("" when checksumming is off)."""
    return write_checksummed(path, state, checksum=checksum)


def _batch_worker(conn, bid, cfg):
    """Batcher child process: decompress + assemble numpy batches."""
    from .connection import force_cpu_jax

    force_cpu_jax()
    from .batch import set_columnar_cache_mb

    set_columnar_cache_mb(cfg.get("columnar_cache_mb"))
    telemetry.configure_from_args(cfg, role=f"batcher-{bid}",
                                  primary=False)
    print(f"started batcher {bid}")
    try:
        while True:
            # jaxlint: disable=unbounded-recv -- batcher child on a parent pipe: learner death breaks the pipe and the except below exits the process
            episodes = conn.recv()
            with telemetry.trace_span("batch.make",
                                      episodes=len(episodes)):
                batch = make_batch(episodes, cfg)
            conn.send(batch)
    except (ConnectionResetError, BrokenPipeError, EOFError, OSError):
        pass  # learner is gone: exit quietly


class Batcher:
    """Parallel batch construction over ``num_batchers`` processes.

    The parent samples episode windows (recency-biased) and ships them
    to child processes that decompress + assemble fixed-shape numpy
    batches (reference train.py:271-319)."""

    def __init__(self, args, episodes, batch_size=None):
        self.args = args
        self.episodes = episodes
        # multi-host: every process's batchers build only its shard of
        # the global batch (batch_size = global / process_count)
        self.batch_size = batch_size or args["batch_size"]
        # children only need the batch-geometry keys, not the env
        # (plus the telemetry keys, so batch.make spans land in the
        # same run's span log)
        cfg = {k: args[k] for k in (
            "turn_based_training", "observation", "forward_steps",
            "burn_in_steps", "compress_steps", "lambda",
            "columnar_cache_mb", "telemetry", "trace_sample_rate",
            "flightrec_spans", "metrics_path",
        ) if k in args}
        transfer = resolve_transfer_dtype(args)
        if transfer:
            cfg["transfer_dtype"] = transfer
        self.executor = MultiProcessJobExecutor(
            _batch_worker, self._selector(), self.args["num_batchers"],
            args_func=lambda i: (i, cfg),
        )

    def _selector(self):
        while True:
            yield [self.select_episode()
                   for _ in range(self.batch_size)]

    def run(self):
        self.executor.start()

    def select_episode(self):
        """Recency-biased sampling: triangular acceptance over buffer
        index, then a random training window with burn-in backoff and
        bz2-block slicing (reference train.py:292-316)."""
        while True:
            ep_count = min(len(self.episodes), self.args["maximum_episodes"])
            ep_idx = random.randrange(ep_count)
            accept_rate = 1 - (ep_count - 1 - ep_idx) / ep_count
            if random.random() >= accept_rate:
                continue
            try:
                ep = self.episodes[ep_idx]
                break
            except IndexError:
                continue
        turn_candidates = 1 + max(
            0, ep["steps"] - self.args["forward_steps"])
        train_st = random.randrange(turn_candidates)
        st = max(0, train_st - self.args["burn_in_steps"])
        ed = min(train_st + self.args["forward_steps"], ep["steps"])
        cmp = self.args["compress_steps"]
        st_block, ed_block = st // cmp, (ed - 1) // cmp + 1
        return {
            "args": ep["args"], "outcome": ep["outcome"],
            "moment": ep["moment"][st_block:ed_block],
            "base": st_block * cmp,
            "start": st, "end": ed, "train_start": train_st,
            "total": ep["steps"],
        }

    def batch(self, timeout=None):
        return self.executor.recv(timeout=timeout)

    def shutdown(self):
        self.executor.shutdown()


from .batch import BF16 as _BF16_NP  # single source for the wire dtype


def resolve_transfer_dtype(args):
    """The observation wire format: 'auto' follows the compute dtype."""
    transfer = args.get("transfer_dtype", "auto") or "auto"
    if transfer == "auto":
        compute = args.get("compute_dtype", "bfloat16") or "bfloat16"
        transfer = "bfloat16" if compute == "bfloat16" else "float32"
    return "" if transfer == "float32" else transfer


@jax.jit
def _debitcast(u16):
    import jax.numpy as jnp

    return jax.lax.bitcast_convert_type(u16, jnp.bfloat16)


@functools.partial(jax.jit, static_argnums=1)
def _dequantize_jit(u8, float_dtype):
    import jax.numpy as jnp

    return u8.astype(jnp.dtype(float_dtype))


_unpack_cache = {}


def _packed_unpack(layout):
    """Jitted column-slicer rebuilding the non-observation leaves from
    one packed (B, C) float32 array; compiled once per batch layout."""
    if layout not in _unpack_cache:
        import jax.numpy as jnp

        def unpack(packed):
            out = {}
            offset = 0
            for key, shape, dtype, width in layout:
                col = jax.lax.slice_in_dim(
                    packed, offset, offset + width, axis=1)
                out[key] = col.reshape(shape).astype(jnp.dtype(dtype))
                offset += width
            return out

        _unpack_cache[layout] = jax.jit(unpack)
    return _unpack_cache[layout]


def _stage_batch_multihost(batch, sharding, obs_float):
    """Multi-process staging: this process's batch shard becomes its
    slice of the global arrays.

    Decode happens on the host (uint8 -> float; bf16 ships natively):
    the single-host uint16-bitcast trick is a jitted computation, and a
    global-array jit is a collective program launch that unsynchronized
    prefetch threads must never issue.  See
    parallel.multihost.global_batch_from_local.
    """
    from .parallel.multihost import global_batch_from_local

    float_np = _BF16_NP if obs_float == "bfloat16" else np.float32

    def decode(a):
        if getattr(a, "dtype", None) == np.uint8:
            return a.astype(float_np)
        return a

    batch = dict(batch)
    batch["observation"] = jax.tree.map(decode, batch["observation"])
    return global_batch_from_local(batch, sharding)


def _stage_batch(batch, sharding, obs_float="bfloat16"):
    """``device_put`` a host batch in its compact wire format and
    restore compute dtypes on device.

    Encodings (all exact):
      * bfloat16 leaves ship as uint16 bit patterns + one on-device
        bitcast.  PJRT's fast memcpy path covers float32 and integer
        dtypes, but numpy bfloat16 falls into an element-wise
        conversion ~8x SLOWER than f32 despite half the bytes
        (measured on TPU v5 lite: 1.2 GB/s f32, 0.15 GB/s bf16,
        1.55 GB/s as uint16).
      * uint8 observation leaves (binary-plane envs, opt-in) ship as
        quarter-width integers and are cast to ``obs_float`` on device.
      * on a single device, the dozen small non-observation leaves are
        packed into ONE (B, C) float32 array and re-sliced by a jitted
        unpack — per-transfer latency (not bandwidth) dominates small
        copies, so 12 round trips become 2 (packed + observation).  Exact: every small leaf is float32
        or a small-integer tensor that round-trips through f32.
    """
    if jax.process_count() > 1:
        return _stage_batch_multihost(batch, sharding, obs_float)
    if sharding is None:
        keys = sorted(k for k in batch if k != "observation")
        cols, layout = [], []
        for key in keys:
            arr = batch[key]
            flat = arr.reshape(arr.shape[0], -1)
            layout.append((key, arr.shape, str(arr.dtype), flat.shape[1]))
            cols.append(flat.astype(np.float32, copy=False))
        packed = jax.device_put(np.concatenate(cols, axis=1))
        staged = _packed_unpack(tuple(layout))(packed)
        obs_host = batch["observation"]
        staged["observation"] = jax.device_put(jax.tree.map(
            lambda a: a.view(np.uint16)
            if getattr(a, "dtype", None) == _BF16_NP else a, obs_host))
    else:
        # multi-chip: per-leaf puts against the batch sharding
        encoded = jax.tree.map(
            lambda a: a.view(np.uint16)
            if getattr(a, "dtype", None) == _BF16_NP else a,
            batch,
        )
        staged = jax.device_put(encoded, sharding)
        staged = {k: v for k, v in staged.items()}
        obs_host = batch["observation"]

    staged["observation"] = jax.tree.map(
        lambda dev, host: _debitcast(dev)
        if getattr(host, "dtype", None) == _BF16_NP else dev,
        staged["observation"], obs_host,
    )
    # uint8 applies to observations only — other integer leaves
    # (actions, masks) keep their dtypes
    staged["observation"] = jax.tree.map(
        lambda dev, host: _dequantize_jit(dev, obs_float)
        if getattr(host, "dtype", None) == np.uint8 else dev,
        staged["observation"], obs_host,
    )
    return staged


class DevicePrefetcher:
    """Stages upcoming batches in device memory from background
    threads, so H2D transfer overlaps the update step's compute and the
    hot loop always finds a device-resident batch waiting.

    Multiple transfer threads pipeline independent ``device_put`` calls
    — batches are independent, so ordering doesn't matter and the
    copies overlap both each other and device compute."""

    def __init__(self, source, depth, sharding=None, threads=2,
                 obs_float="bfloat16"):
        self.source = source          # callable(timeout=) -> host batch
        self.sharding = sharding      # None = default device
        self.obs_float = obs_float    # decode dtype for uint8 obs
        self.staged = queue.Queue(maxsize=max(1, depth))
        self.stop_flag = False
        self.error = None
        self.threads = [
            threading.Thread(target=self._pump, daemon=True)
            for _ in range(max(1, threads))
        ]
        for thread in self.threads:
            thread.start()

    def _pump(self):
        try:
            while not self.stop_flag:
                try:
                    batch = self.source(timeout=0.3)
                except queue.Empty:
                    continue
                batch = _stage_batch(batch, self.sharding, self.obs_float)
                while not self.stop_flag:
                    try:
                        self.staged.put(batch, timeout=0.3)
                        break
                    except queue.Full:
                        continue
        except Exception as exc:  # surface in the trainer, don't hang it
            self.error = exc

    def get(self, timeout=None):
        try:
            return self.staged.get(timeout=timeout)
        except queue.Empty:
            if self.error is not None:
                raise RuntimeError("device prefetch failed") from self.error
            raise

    def stop(self):
        self.stop_flag = True
        # don't let interpreter teardown race an in-flight device_put
        for thread in self.threads:
            thread.join(timeout=5)


def _sum_steps(per_step):
    """An epoch's per-step ``metrics`` (host numbers) -> what
    ``Trainer._finish_epoch`` reads of them, in the form the fused
    replay step carries on the device (``staging.epoch_sums``): each
    key's sum over the steps, and ``steps``, their count."""
    from .staging import COUNT_SUMS, LOSS_SUMS

    keys = LOSS_SUMS + COUNT_SUMS + ("anakin_frames", "anakin_games")
    sums = {"steps": len(per_step)}
    for metrics in per_step:
        for key in keys:
            if key in metrics:
                sums[key] = sums.get(key, 0.0) + float(metrics[key])
    return sums


class Trainer:
    """Owns device state (params + optimizer) and the jitted step."""

    def __init__(self, args, model: TPUModel):
        self.episodes = deque()
        self.args = args
        self.model = model
        self.loss_cfg = LossConfig.from_config(args)
        self.compute_dtype = args.get("compute_dtype") or "bfloat16"
        self.default_lr = DEFAULT_LR
        self.data_cnt_ema = args["batch_size"] * args["forward_steps"]
        self.num_params = len(jax.tree.leaves(model.params or {}))
        self.epoch = args.get("restart_epoch", 0)
        self.steps = 0
        self.update_flag = False
        self.shutdown_flag = False
        self.failure = None
        self.stall_beat = None   # StallWatchdog beat (set by Learner)
        # durability: checkpoint writes stamp checksums, saves report
        # their digest for the manifest (set by Learner), and a SIGTERM
        # grace window can request an emergency save between steps
        self.checkpoint_checksum = bool(
            args.get("checkpoint_checksum", True))
        self.manifest = None       # CheckpointManifest (set by Learner)
        self.last_state_digest = ""
        self.emergency = None      # threading.Event armed by SIGTERM
        self.update_queue = queue.Queue(maxsize=1)
        # multi-host: this process is one controller of a global mesh;
        # its feed builds 1/process_count of every global batch
        self.multihost = jax.process_count() > 1
        self.primary = jax.process_index() == 0
        self.updates_cap = int(args.get("updates_per_epoch", 0) or 0)
        self.local_batch_size = args["batch_size"]
        if self.multihost:
            from .parallel.multihost import local_batch_size

            self.local_batch_size = local_batch_size(args["batch_size"])
        self.batch_sharding = None
        self.train_mesh = None
        self.train_fsdp = False
        self._replicate_jit = None
        self.prefetcher = None
        self.timers = SectionTimers()
        # how far ahead of the device this thread runs, and when the
        # device had no step (telemetry.inflight): fed where a loop
        # dispatches a step, polled at edges the thread already has
        self.inflight = telemetry.InFlight()
        self.trace = TraceWindow(self.args.get("profile_dir") or "",
                                 hlo_text=self._step_hlo_text)
        self._run_thread = None       # the thread inside run(), if any
        self._step_profile = None     # step_profile()'s cached answer
        # compile accounting for the hot-path programs: the update step
        # must compile once per run (per mesh shape); anything more is
        # shape churn.  max_update_compiles > 0 turns the count into a
        # hard assertion checked after every step
        self.retrace_guard = RetraceGuard(
            max_compiles=self.args.get("max_update_compiles", 0),
            name="update_step")
        # runtime MFU/roofline accounting (telemetry.costmodel): the
        # guard's on_compile hook harvests XLA's own flops/bytes for
        # each step program at its (rare) new-signature moments, and
        # train() reduces them into per-epoch mfu/achieved_tflops/
        # roofline keys next to the guard counters, every run, over
        # the seconds in which a step was in flight
        from .telemetry.costmodel import CostModel, PerfConfig

        self.costmodel = CostModel(
            PerfConfig.from_config(self.args.get("perf") or {}))
        self.retrace_guard.on_compile = self._on_step_compile
        self._step_label = "update_step"  # the active step program
        # the schedule the step's value targets took at its latest
        # lowering and the time axis's length that chose it
        # (ops.targets): {"form": "sequential" | "log_depth", "length"};
        # None until a harvest has traced a step that computes targets
        self.targets_scan = None
        self.transfer_guard = (
            HostTransferGuard()
            if self.args.get("host_transfer_guard", True) else None)
        # sharding contract: the update step's arguments must keep the
        # layout of their first call — any later deviation is a silent
        # XLA resharding copy per step (and defeats donation), reported
        # per epoch as `resharding_copies` next to the retrace count
        self.shard_guard = (
            ShardingContractGuard(
                max_copies=self.args.get("max_resharding_copies", 0),
                name="update_step")
            if self.args.get("sharding_contract_guard", True) else None)
        # numerics contract: the update step's arguments must keep the
        # per-leaf dtype/weak-type of their first call, and the step's
        # in-graph loss/grad-norm finiteness flag must stay 0 — the
        # runtime twin of numlint (analysis/numlint.py), reported per
        # epoch as numerics_contract_breaks / nonfinite_steps /
        # weak_upcasts
        self.num_guard = (
            NumericsGuard(
                max_nonfinite=self.args.get("max_nonfinite_steps", 0),
                name="update_step")
            if self.args.get("numerics_guard", True) else None)

        # off-policy robustness (IMPACT): the update step threads a
        # target network whose params start as an exact copy of the
        # live params; checkpoints carry it so resume is exact
        self.impact = str(args.get("update_algorithm", "standard")
                          or "standard") == "impact"
        self.target_params = None
        if self.num_params > 0:
            self.optimizer = make_optimizer(
                self.default_lr * self.data_cnt_ema)
            # a private copy: the update step DONATES its params, and
            # the learner still serves ``model`` (epoch 0) to workers
            # that ask for it after the first step
            self.params = jax.tree.map(jax.numpy.array, model.params)
            # ... and the served copy goes to the host, where every
            # later epoch's snapshot lives: beside a train state of
            # gigabytes a second set of parameters does not fit the chip
            model.params = jax.tree.map(np.asarray, model.params)
            self.opt_state = self.optimizer.init(self.params)
            if self.impact:
                self.target_params = jax.tree.map(np.asarray, self.params)
            self.update_step = self._guarded(
                self._build_update_step(), "update_step")
            self._maybe_restore_train_state()
            if self.multihost:
                self._sync_initial_state()
        else:
            self.optimizer = None

        # Anakin mode (handyrl_tpu.anakin): for envs with a pure-JAX
        # twin, rollout + batch assembly + update run as ONE jitted
        # program per step — generation leaves the worker fleet
        # entirely (workers only evaluate), so the replay machinery
        # below is skipped
        self.anakin = None
        self._anakin_step = None
        self.anakin_carry = None
        self.anakin_pool = None
        self.anakin_frames_total = 0.0
        self.anakin_games_total = 0.0
        if self.optimizer is not None:
            self._maybe_build_anakin()

        self.device_replay = (None if self.anakin is not None
                              else self._maybe_device_replay())
        if self.device_replay is not None:
            # ingest runs on this thread, between two dispatches
            self.device_replay.inflight = self.inflight
        self._replay_step = None
        if self.device_replay is not None and not self.multihost:
            # ONE jitted program per step: draw + gather + loss + grad
            # + Adam — the host passes three scalars (multi-host
            # instead assembles global batches from the local rings
            # and runs the global update_step)
            self._replay_step = self._guarded(
                self._make_replay_step(), "replay_step")
            self._step_label = "replay_step"
        # the host batcher farm exists only when the device-resident
        # path is off: skipping it frees host cores for actors
        self.batcher = None
        if (self.optimizer is not None and self.device_replay is None
                and self.anakin is None):
            self.batcher = Batcher(self.args, self.episodes,
                                   batch_size=self.local_batch_size)

    def _maybe_build_anakin(self):
        """Arm the fused on-device rollout+update (Anakin, ROADMAP
        item 2) when configured AND the env has a pure-JAX twin.

        ``anakin.mode: on`` makes an unusable setup an error;
        ``auto`` falls back loudly to the IMPALA worker path (remote
        workers, multi-host replicas, and envs without a registered
        JAX twin all keep the worker path).  The fused step rides the
        same RetraceGuard/ShardingContractGuard as the other update
        paths: exactly one compile per run, zero resharding copies."""
        from .anakin import AnakinConfig, AnakinEngine
        from .environment import jax_env_available, make_jax_env

        acfg = AnakinConfig.from_config(self.args.get("anakin") or {})
        if not acfg.enabled:
            return
        env_args = self.args.get("env") or {}
        if self.multihost:
            msg = ("anakin mode is single-process (multi-host learners "
                   "keep the IMPALA path)")
        elif not jax_env_available(env_args):
            msg = (f"env {env_args.get('env')!r} has no pure-JAX twin "
                   "in JAX_ENV_REGISTRY")
        else:
            msg = None
        if msg:
            if acfg.mode == "on":
                raise ValueError("anakin.mode: on — " + msg)
            print(f"WARNING: {msg}; falling back to the IMPALA "
                  "worker path")
            return
        if self.train_mesh is not None:
            dp = int(self.train_mesh.shape.get("dp", 1)) or 1
            if acfg.num_envs % dp != 0:
                raise ValueError(
                    f"anakin.num_envs {acfg.num_envs} must be "
                    f"divisible by the mesh dp axis ({dp}): the env "
                    "axis is the fused step's batch dimension")
        try:
            self.anakin = AnakinEngine(
                make_jax_env(env_args), self.model, self.loss_cfg,
                self.optimizer, acfg, compute_dtype=self.compute_dtype,
                seed=self.args.get("seed", 0), mesh=self.train_mesh,
                params=self.params, fsdp=self.train_fsdp)
        except ValueError as exc:
            # layout constraints (recurrent net, observation mode,
            # burn-in, short unroll) make anakin UNAVAILABLE, which is
            # exactly what auto falls back on; `on` means require it
            if acfg.mode == "on":
                raise
            print(f"WARNING: anakin unavailable ({exc}); falling "
                  "back to the IMPALA worker path")
            return
        self._anakin_step = self._guarded(
            self.anakin.make_fused_step(), "anakin_step")
        self._step_label = "anakin_step"
        # the carry folds the resumed step count into its PRNG stream,
        # so a restart continues on fresh data deterministically
        self.anakin_carry = self.anakin.init_carry(self.steps)
        self.anakin_pool = self.anakin.init_pool(self.params)
        print(f"anakin mode: {self.anakin.num_envs} on-device games x "
              f"{self.anakin.unroll}-step segments"
              + (f", opponent pool {self.anakin.K}"
                 if self.anakin.K else " (pure self-play)"))

    def _guarded(self, step, label):
        """A jitted step as the hot loops call it: its launches ordered
        with the inference service's over the training mesh, inside the
        numerics, sharding and retrace guards."""
        step = OrderedLaunch(step, self.train_mesh)
        if self.num_guard is not None:
            step = self.num_guard.wrap(step)
        if self.shard_guard is not None:
            step = self.shard_guard.wrap(step)
        return self.retrace_guard.wrap(step, label=label)

    def _maybe_device_replay(self):
        """Build the HBM-resident replay (staging.DeviceReplay) when
        configured (auto = on).

        Multi-host: each process keeps its OWN ring over a LOCAL mesh
        of its addressable devices; the gather emits this process's
        per-device batch shards (rows on local dp groups, replicated
        across the sp*tp axes inside each group), and
        ``_epoch_loop_multihost`` assembles them into global arrays
        without any cross-host data movement.  Works on any
        dp/sp/tp/fsdp mesh whose dp groups are process-local;
        otherwise falls back to the host batcher path."""
        mode = self.args.get("device_replay", "auto") or "auto"
        if self.optimizer is None or mode == "off":
            return None
        mesh = self.train_mesh
        if self.multihost:
            # Local-shard assembly works for ANY (dp, sp, tp[, fsdp])
            # mesh whose dp groups are process-local.  Batch rows shard
            # over dp and REPLICATE across sp/tp; the global mesh is
            # jax.devices() (process-major) reshaped row-major to
            # (dp, sp, tp), so dp coordinate d owns the `rep = sp*tp`
            # consecutive devices [d*rep, (d+1)*rep).  When rep divides
            # the local device count, every replication group lives on
            # one process: the local ring gathers each dp-block of rows
            # ONCE and lays it out replicated across that group, and
            # global assembly is pure metadata (the rows are already on
            # the right devices with the right replication).
            from .parallel import multihost as mh

            n_local = jax.local_device_count()
            local_bs = self.local_batch_size
            rep = 1 if mesh is None else mh.replay_group_size(mesh)
            msg = None
            if mesh is None or mesh.size != jax.device_count():
                msg = ("multi-host device replay requires a mesh over "
                       "all devices")
            elif n_local % rep != 0:
                msg = (f"multi-host device replay requires each dp "
                       f"group (sp*tp = {rep} devices) to be "
                       f"process-local; {n_local} local devices is "
                       f"not a multiple of {rep}")
            elif local_bs % (n_local // rep) != 0:
                msg = (f"device replay needs local batch {local_bs} "
                       f"divisible by {n_local // rep} local dp groups")
            if msg:
                if mode == "on":
                    raise ValueError(msg)
                # LOUD: in a pod launch log a one-line note is easy to
                # miss, and the host batcher feed is ~13x slower
                print("WARNING: " + msg + " — falling back to the "
                      "host batcher path (measured ~13x slower feed); "
                      "set device_replay: on to make this an error")
                return None
            mesh = mh.local_replay_mesh(mesh)
        from .staging import DeviceReplay

        cfg = {
            "turn_based_training": self.args["turn_based_training"],
            "observation": self.args.get("observation", False),
            "forward_steps": self.args["forward_steps"],
            "burn_in_steps": self.args.get("burn_in_steps", 0),
            "transfer_dtype": resolve_transfer_dtype(self.args),
            "compute_dtype": self.compute_dtype,
        }
        capacity = (self.args.get("device_replay_episodes", 0)
                    or self.args["maximum_episodes"])
        max_bytes = (self.args.get("device_replay_mb", 4096)
                     or 4096) << 20
        return DeviceReplay(cfg, capacity, max_bytes, mesh=mesh)

    def _sync_initial_state(self):
        """Broadcast process 0's full train state so replicas provably
        start identical — required when only process 0 could read a
        restart checkpoint, and cheap insurance against any per-host
        init drift.  One-time collective at startup."""
        from .parallel.multihost import broadcast_train_state

        self.params, self.opt_state, self.steps, self.data_cnt_ema = (
            broadcast_train_state(
                self.params, self.opt_state, self.steps,
                self.data_cnt_ema))
        if self.target_params is not None:
            # the target net rides the same one-time broadcast (in the
            # params slot; the other slots are placeholders)
            self.target_params = broadcast_train_state(
                self.target_params, (), 0, 0.0)[0]
        if self.train_mesh is not None:
            self._place_global_state()

    def _place_global_state(self):
        """Lay the (host-replicated) params + optimizer state out on
        their global-mesh shardings.  Multi-process jit refuses numpy
        arguments whose in_sharding is non-trivial (e.g. an
        fsdp-sharded kernel), so unlike the single-host path the
        placement must happen explicitly: every process materializes
        its addressable shards from its identical host copy — no
        cross-host data movement."""
        from .parallel import param_sharding, replicated
        from .parallel.update import opt_state_sharding

        p_shard = param_sharding(self.train_mesh, self.params,
                                 fsdp=self.train_fsdp)
        rep = replicated(self.train_mesh)
        o_shard = opt_state_sharding(
            self.optimizer, self.params, p_shard, rep)

        def place(tree, shards):
            return jax.tree.map(
                lambda a, s: jax.make_array_from_callback(
                    np.shape(a), s,
                    lambda idx, a=a: np.asarray(a)[idx]),
                tree, shards)

        self.params = place(self.params, p_shard)
        self.opt_state = place(self.opt_state, o_shard)
        if self.target_params is not None:
            self.target_params = place(self.target_params, p_shard)

    def _maybe_restore_train_state(self):
        """Resume optimizer state on restart (the reference checkpoints
        the model only — restoring Adam moments + the lr EMA makes
        restarts seamless instead of re-warming the optimizer)."""
        restart_epoch = self.args.get("restart_epoch", 0)
        if not isinstance(restart_epoch, int) or restart_epoch <= 0:
            return
        try:
            # when the resume point carries a manifest-recorded train-
            # state digest, require the file on disk to BE that file:
            # the epoch tag alone cannot tell a boundary save from a
            # later emergency save of the same epoch, and restoring
            # the wrong one would pair params with a different step's
            # optimizer moments (silently breaking exact resume)
            state = read_verified(
                train_state_path(),
                expect_digest=self.args.get("_resume_state_digest")
                or None)
        except OSError:
            return  # missing: cold-start the optimizer
        except CorruptCheckpointError as exc:
            # truncated / bit-flipped / not the state this resume
            # point's params were saved with: refusing to trust it is
            # the whole point of the digest — cold-start LOUDLY
            print(f"WARNING: train state failed verification ({exc}); "
                  "cold-starting the optimizer")
            return
        if state.get("epoch") != restart_epoch:
            # optimizer state belongs to a different epoch's params
            print("train state is for epoch %s, not %d: cold-starting"
                  % (state.get("epoch"), restart_epoch))
            return
        try:
            # read everything into temporaries first so a mismatch on a
            # later key cannot leave a half-restored optimizer behind
            opt_state = jax.tree.map(
                lambda like, saved: jax.numpy.asarray(saved),
                self.opt_state, state["opt_state"])
            steps = state["steps"]
            data_cnt_ema = state["data_cnt_ema"]
            target_params = None
            if self.target_params is not None \
                    and state.get("target_params") is not None:
                target_params = jax.tree.map(
                    lambda like, saved: jax.numpy.asarray(saved),
                    self.target_params, state["target_params"])
        except (ValueError, TypeError, KeyError):
            # pytree structure changed (e.g. the net was modified
            # between runs): cold-start rather than crash at startup
            print("train state does not match the current model: "
                  "cold-starting the optimizer")
            return
        self.opt_state = opt_state
        self.steps = steps
        self.data_cnt_ema = data_cnt_ema
        if target_params is not None:
            self.target_params = target_params
        elif self.target_params is not None:
            # checkpoint predates the target net (algorithm switched
            # on between runs): start it from the restored params
            print("no target params in train state: target network "
                  "starts as a copy of the restored model")
        print(f"restored optimizer state at step {self.steps}")

    def save_train_state(self, epoch, host_opt_state=None,
                         host_target=None):
        if host_opt_state is None:
            host_opt_state = self._to_host(self.opt_state)
        state = {
            "opt_state": host_opt_state,
            "steps": self.steps,
            "data_cnt_ema": self.data_cnt_ema,
            "epoch": epoch,
        }
        if self.target_params is not None:
            # the target net is train state: resuming without it would
            # silently restart the off-policy correction from the live
            # params (multihost passes the collectively-fetched copy)
            state["target_params"] = (
                host_target if host_target is not None
                else self._to_host(self.target_params))
        self.last_state_digest = write_atomic(
            train_state_path(), state,
            checksum=self.checkpoint_checksum)

    def _maybe_emergency_save(self):
        """SIGTERM grace window: the handler (Learner._preempt_save)
        armed ``self.emergency`` and is waiting on it; land a
        CONSISTENT mid-epoch checkpoint — current params as
        ``latest.ckpt`` plus the matching optimizer train state — and
        re-point the manifest at it as an emergency resume point.
        Runs on the trainer thread between steps (the only thread that
        may touch the donated device state).  Skipped (event still
        set) when there is nothing resumable yet (no completed epoch:
        the resume machinery keys on epoch >= 1) or when saving is not
        this process's job (multihost replicas; collectives are unsafe
        inside a grace window, so multihost relies on the boundary
        checkpoint instead)."""
        event = self.emergency
        if event is None or event.is_set():
            return
        try:
            if (self.optimizer is None or self.multihost
                    or not self.primary or self.epoch < 1
                    or self.steps <= 0):
                return
            params = self._to_host(self.params)
            state = {"params": params, "steps": self.steps,
                     "epoch": self.epoch}
            os.makedirs(_models_dir(), exist_ok=True)
            digest = write_atomic(latest_model_path(), state,
                                  checksum=self.checkpoint_checksum)
            self.save_train_state(self.epoch)
            if self.manifest is not None:
                self.manifest.commit(
                    self.epoch, latest_model_path(), digest,
                    self.steps,
                    train_state_digest=self.last_state_digest,
                    emergency=True)
            print(f"emergency checkpoint landed (epoch {self.epoch}, "
                  f"step {self.steps})")
        finally:
            event.set()

    def _to_host(self, tree):
        """Host numpy copy of a device pytree.  Leaves that shard
        across processes (fsdp/tp on a multi-host mesh) cannot be read
        directly; one jitted identity re-lays them out replicated
        first — an XLA all-gather over ICI.  That makes this a
        COLLECTIVE whenever such leaves exist: every process must call
        it at the same point (train() does, once per epoch)."""
        leaves = jax.tree.leaves(tree)
        if self.multihost and self.train_mesh is not None and any(
                not getattr(l, "is_fully_replicated", True)
                for l in leaves):
            if self._replicate_jit is None:
                from .parallel import replicated

                # one persistent jit: each pytree structure compiles
                # its all-gather once, not once per epoch
                self._replicate_jit = jax.jit(
                    lambda t: t,
                    out_shardings=replicated(self.train_mesh))
            tree = self._replicate_jit(tree)
        return jax.tree.map(np.asarray, tree)

    def _default_mesh_cfg(self):
        """With no mesh configured on a multi-device host, default to
        pure data parallelism over as many devices as divide the batch
        (the reference auto-engages DataParallel the same way)."""
        n_dev = jax.device_count()
        if n_dev <= 1:
            return {}
        batch = self.args["batch_size"]
        # largest divisor of the batch that fits the host, so an odd
        # batch size degrades gracefully instead of to gcd-of-2
        dp = max(d for d in range(1, n_dev + 1) if batch % d == 0)
        if dp <= 1:
            print(f"1 of {n_dev} devices used: batch_size "
                  f"{batch} has no divisor <= {n_dev}")
            return {}
        if dp < n_dev:
            print(f"WARNING: dp={dp} leaves {n_dev - dp} of {n_dev} "
                  f"devices idle; make batch_size divisible by {n_dev} "
                  f"or set an explicit mesh")
        print(f"defaulting to dp={dp} over {n_dev} devices")
        return {"dp": dp}

    def _check_sequence_net(self, mesh_cfg):
        """What a net whose window is the sequence cannot be built
        with: input checks, each refused with its reason."""
        length = self.model.module.sequence_length
        if int(self.args.get("burn_in_steps", 0) or 0) > 0:
            raise ValueError(
                "a sequence net takes the whole window in one causal "
                "pass and carries no state to warm: burn_in_steps must "
                f"be 0 (it is {self.args['burn_in_steps']})")
        if length > self.args["forward_steps"]:
            raise ValueError(
                f"an episode of this net can be {length} steps long and "
                f"a window holds forward_steps = "
                f"{self.args['forward_steps']}: windows start at "
                "position 0 and must hold the whole sequence")
        if any(int(v) > 1 for k, v in mesh_cfg.items() if k != "fsdp"):
            raise ValueError(
                f"mesh {mesh_cfg} asks for more than one device and a "
                "sequence net's experts have no axis to be divided "
                "over yet (parallel/mesh.py): it trains on one device")

    def _build_update_step(self):
        dtype = self.compute_dtype
        print(f"compute dtype: {dtype}")
        mesh_cfg = dict(self.args.get("mesh") or {})
        axes_cfg = {k: v for k, v in mesh_cfg.items() if k != "fsdp"}
        if self.model.is_sequence:
            self._check_sequence_net(mesh_cfg)
        elif not axes_cfg:
            # only auto-shard when the user left the mesh AXES unset
            # (a bare {fsdp: true} still engages auto-dp); an explicit
            # all-ones mesh (e.g. {dp: 1}) forces the unsharded step
            default = self._default_mesh_cfg()
            if default:
                mesh_cfg = {**default,
                            "fsdp": mesh_cfg.get("fsdp", False)}
            elif mesh_cfg.get("fsdp"):
                print("WARNING: mesh {fsdp: true} ignored — no "
                      "multi-device dp axis available")
        engaged = any(int(v) > 1 for k, v in mesh_cfg.items()
                      if k != "fsdp")
        if self.multihost and not engaged:
            raise ValueError(
                "multi-host training requires a multi-device mesh: set "
                "`mesh:` explicitly or make batch_size divisible by the "
                "global device count")
        if engaged:
            from .parallel import (
                MeshSpec,
                batch_sharding,
                make_mesh,
                make_sharded_update_step,
            )

            spec = MeshSpec.from_config(mesh_cfg)
            mesh = make_mesh(spec)
            self.train_mesh = mesh
            self.train_fsdp = spec.fsdp
            self.batch_sharding = batch_sharding(mesh)
            return make_sharded_update_step(
                self.model, self.loss_cfg, self.optimizer, mesh,
                self.params, shard_time=spec.sp > 1, compute_dtype=dtype,
                fsdp=spec.fsdp,
            )
        return make_update_step(
            self.model, self.loss_cfg, self.optimizer, compute_dtype=dtype)

    def update(self):
        """Called by the Learner: finish the epoch, get a snapshot.

        Returns ``(None, steps)`` if the training thread has died —
        the learner then keeps serving the last model instead of
        blocking forever on a queue no one will fill."""
        self.update_flag = True
        while True:
            if self.stall_beat is not None:
                # the caller IS the server loop: keep its watchdog fed
                # while a long epoch finishes, so "slow epoch" and
                # "wedged server" stay distinguishable
                self.stall_beat("server")
            try:
                return self.update_queue.get(timeout=1)
            except queue.Empty:
                if self.failure is not None or self.shutdown_flag:
                    return None, self.steps

    @contextmanager
    def _dispatching(self):
        """The ``update`` section of a loop: the dispatch timed as
        ever, its span given the ledger's ``depth`` and ``done``."""
        attrs = {}
        with self.timers.section("update", attrs=attrs), \
                self.inflight.watch("update", attrs):
            yield

    def _do_update(self, batch):
        with self._dispatching():
            if self.target_params is not None:
                (self.params, self.opt_state, metrics,
                 self.target_params) = self.update_step(
                    self.params, self.opt_state, batch,
                    self.target_params)
            else:
                self.params, self.opt_state, metrics = self.update_step(
                    self.params, self.opt_state, batch)
            self.inflight.launch(metrics["total"])
        self.trace.tick()
        self.steps += 1
        return metrics

    def _epoch_loop_local(self):
        """Single-process epoch: train until the learner asks for the
        snapshot (and at least one batch has landed)."""
        cap = self.updates_cap
        batch_cnt, metric_acc = 0, []
        while batch_cnt == 0 or not self.update_flag:
            if self.shutdown_flag:
                return None
            self._maybe_emergency_save()
            if cap and batch_cnt >= cap:
                time.sleep(0.01)
                continue
            try:
                with self.timers.section("batch_wait"):
                    batch = self.prefetcher.get(timeout=0.3)
            except queue.Empty:
                continue
            # keep metrics on device; sync once per epoch
            metric_acc.append(self._do_update(batch))
            batch_cnt += 1
        return batch_cnt, metric_acc

    def _epoch_loop_device(self):
        """Device-replay epoch: draw + gather + update run as ONE
        jitted program per step fed three host scalars; the host only
        drains newly arrived episodes into the ring (bounded per
        step)."""
        from .staging import step_state

        replay = self.device_replay
        cap = self.updates_cap
        batch_cnt = 0
        state = metrics = None
        while batch_cnt == 0 or not self.update_flag:
            if self.shutdown_flag:
                return None
            self._maybe_emergency_save()
            with self.timers.section("ingest", span=False):
                # drain arrivals even when idling at the cap, so the
                # pending queue can't overflow and shed episodes; the
                # seconds of every call feed profile_ingest_sec, the
                # span is ingest's own (none for an empty call).  One
                # scatter's worth between two steps: 8 episodes at a
                # board game's window, 4 at a window of thousands of
                # steps, whose episodes take a tenth of a second each
                # to unpack (8 of them outlast the step, and the
                # device waits)
                replay.ingest(max_episodes=replay.max_run)
            # ring growth re-lays the buffers (new shapes): those
            # recompiles are designed, so they widen the retrace
            # budget instead of tripping it
            self.retrace_guard.allowance = replay.growths
            if cap and batch_cnt >= cap:
                # epoch budget spent: idle until the learner asks for
                # the snapshot, releasing host CPU to the actors
                self.inflight.poll("cap")
                time.sleep(0.01)
                continue
            if state is None:
                # every epoch starts as the run's first did: the
                # ring's scalars uploaded, the sums at zero
                state = step_state(replay, self.steps)
            elif replay.state_dirty:
                # one tiny upload per ring change, of the ring's half
                # alone: the epoch's sums stay where they are.  Between
                # changes the whole state lives on device and rides
                # the jit
                state = replay.device_state(self.steps), state[1]
            with self._dispatching():
                # the step's own metrics are dropped as they return:
                # the boundary reads the sums the step carries; the
                # ledger keeps one scalar of them, which no call
                # donates, until the device has run the step
                metrics, state = self._fused_step(state)
                self.inflight.launch(metrics["total"])
            self.trace.tick()
            batch_cnt += 1
        # of the sums, those this step program adds to (a net with no
        # return head has no "r"): the keys of any step's metrics
        return batch_cnt, {key: total for key, total in state[1].items()
                           if key == "steps" or key in metrics}

    def _fused_step(self, state):
        """Dispatch ONE fused replay step on the live params and ring;
        returns ``(metrics, state)`` with the draw state advanced."""
        replay = self.device_replay
        # the step's scopes are read back from its compiled text: its
        # cache entry is keyed with them (a compile, if this call is one)
        with metadata_in_key():
            if self.target_params is not None:
                (self.params, self.opt_state, metrics, state,
                 self.target_params) = self._replay_step(
                    self.params, self.opt_state, replay.buffers,
                    state, self.target_params)
            else:
                (self.params, self.opt_state,
                 metrics, state) = self._replay_step(
                    self.params, self.opt_state, replay.buffers, state)
        self.steps += 1
        return metrics, state

    def _make_replay_step(self):
        from .staging import make_replay_update_step

        return make_replay_update_step(
            self.device_replay, self.model, self.loss_cfg,
            self.optimizer, self.compute_dtype,
            batch_size=self.args["batch_size"],
            mesh=self.train_mesh, params=self.params,
            fsdp=self.train_fsdp, seed=self.args.get("seed", 0))

    def _on_step_compile(self, label, fn, args, kwargs):
        """A step program at a new signature (``RetraceGuard``'s hook):
        the cost model harvests its lowering, and what the targets'
        recursion noted while that lowering traced it is kept."""
        with noting_targets() as notes:
            self.costmodel.on_compile(label, fn, args, kwargs)
        if notes:
            self.targets_scan = notes[-1]

    def _step_hlo_text(self):
        """HLO text of the compiled step program, from the cost model's
        harvest: where a device trace's op events find their
        ``jax.named_scope`` (telemetry/devtrace.py)."""
        return self.costmodel.hlo_text(self._step_label)

    def step_profile(self, steps=16):
        """The fused replay step's device time by phase: ``steps`` steps
        on the live params and ring under a private profiler session,
        reduced by ``telemetry.devtrace.step_phases`` to ``{steps,
        step_ms, phases: {gather, forward, targets, backward, optimizer,
        unscoped}, scopes, kernel_ms, counters, targets_scan}`` (ms per step;
        ``scopes``: the net's own named scopes, forward and transpose
        together; ``kernel_ms``: what of them ran in hand-written
        kernels; ``counters``: what the profiled steps counted beside
        their losses, ``ops.losses.SEQUENCE_COUNTERS``; all three empty
        for a net that has none); the phases are printed, the trace is
        deleted, the answer cached.  Only once the trainer thread has
        ended, or from it: the step donates the state that thread owns.
        Never raises: a failure (no fused step, no TPU plane in the
        trace, a profiler session already open) prints one line and
        returns None."""
        if self._step_profile is None:
            try:
                self._step_profile = self._capture_step_profile(steps)
            except Exception as exc:
                print(f"step profile not taken ({exc!r})")
                self._step_profile = False
        return self._step_profile or None

    def _capture_step_profile(self, steps):
        import shutil
        import tempfile

        from .staging import step_state
        from .telemetry import devtrace
        from .utils.profiling import profiler_options

        replay = self.device_replay
        if (self._replay_step is None or replay is None
                or replay.buffers is None):
            raise RuntimeError("no fused replay step on a filled ring")
        if self._run_thread not in (None, threading.current_thread()):
            raise RuntimeError("the trainer thread is running")
        hlo = self._step_hlo_text()
        state = step_state(replay, self.steps)
        trace_dir = tempfile.mkdtemp(prefix="hrl-step-profile-")
        try:
            jax.profiler.start_trace(
                trace_dir, profiler_options=profiler_options())
            counted = []
            try:
                for _ in range(steps):
                    metrics, state = self._fused_step(state)
                    counted.append({k: metrics[k] for k in SEQUENCE_COUNTERS
                                    if k in metrics})
                jax.block_until_ready(state)
            finally:
                jax.profiler.stop_trace()
            trace = devtrace.load(devtrace.find_xplane(trace_dir), hlo)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        profile = devtrace.step_phases(trace)
        print("step phases = %s" % devtrace.format_phases(profile))
        counted = jax.device_get(counted)
        # the fullest expert of any profiled step; the others as means
        profile["counters"] = {
            k: float((max if k.endswith("_max") else np.mean)(
                [c[k] for c in counted])) for k in counted[0]}
        profile["targets_scan"] = self.targets_scan
        return profile

    def _epoch_loop_anakin(self):
        """Anakin epoch: self-play rollout, batch assembly, and the
        optimizer update are ONE jitted program per step (donated
        params/optimizer/carry; the opponent pool rides read-only).
        The host dispatches the call and nothing else — no intake, no
        ring, no prefetch; ``updates_per_epoch`` (required > 0) is the
        epoch budget, after which the loop idles until the learner
        asks for the snapshot."""
        cap = self.updates_cap
        batch_cnt, metric_acc = 0, []
        while batch_cnt == 0 or not self.update_flag:
            if self.shutdown_flag:
                return None
            self._maybe_emergency_save()
            if cap and batch_cnt >= cap:
                time.sleep(0.01)
                continue
            t0 = telemetry.span_begin()
            with self._dispatching():
                if self.target_params is not None:
                    (self.params, self.opt_state, metrics,
                     self.anakin_carry,
                     self.target_params) = self._anakin_step(
                        self.params, self.opt_state, self.anakin_carry,
                        self.anakin_pool, self.target_params)
                else:
                    (self.params, self.opt_state, metrics,
                     self.anakin_carry) = self._anakin_step(
                        self.params, self.opt_state, self.anakin_carry,
                        self.anakin_pool)
                self.inflight.launch(metrics["total"])
            # static attrs only: the committed frame count is a device
            # scalar, and fetching it here would be a per-step host
            # sync (it rides the metrics fetch at the epoch boundary)
            telemetry.span_end("anakin.rollout", t0,
                               games=self.anakin.num_envs,
                               unroll=self.anakin.unroll)
            self.trace.tick()
            self.steps += 1
            metric_acc.append(metrics)
            batch_cnt += 1
        return batch_cnt, metric_acc

    def _global_from_local_shards(self, local_batch):
        """Assemble global batch arrays from this process's local
        per-device shards (device replay under multi-host).  Pure
        metadata: the shards stay where the local gather put them."""
        from .parallel import multihost as mh

        return mh.global_from_local_shards(
            local_batch, self.batch_sharding)

    def _next_multihost_batch(self):
        """One committed step's batch: device replay (local ring ->
        global assembly) or the host prefetcher."""
        if self.device_replay is not None:
            with self.timers.section("ingest", span=False):
                self.device_replay.ingest(
                    max_episodes=self.device_replay.max_run)
            # growth recompiles are designed: widen the retrace budget
            self.retrace_guard.allowance = self.device_replay.growths
            with self.timers.section("batch_wait"):
                local = self.device_replay.sample(self.local_batch_size)
                return self._global_from_local_shards(local)
        while True:
            try:
                with self.timers.section("batch_wait"):
                    return self.prefetcher.get(timeout=1)
            except queue.Empty:
                continue

    def _epoch_loop_multihost(self):
        """Multi-process epoch: process 0 decides, everyone executes the
        same step count.  Each iteration syncs one control word (STEP /
        EPOCH_END / STOP) — the same collective doubles as the step
        barrier, so every process's jitted-call sequence is identical
        by construction (the SPMD contract)."""
        from .parallel import multihost as mh

        cap = self.updates_cap
        batch_cnt, metric_acc = 0, []
        while True:
            if self.primary and cap and batch_cnt >= cap:
                # epoch budget spent: hold the next control sync until
                # the learner asks for the snapshot (replicas simply
                # wait in the collective)
                while not (self.update_flag or self.shutdown_flag
                           or self.failure is not None):
                    time.sleep(0.01)
            code = mh.STEP
            if self.primary:
                if self.shutdown_flag or self.failure is not None:
                    code = mh.STOP
                elif batch_cnt > 0 and self.update_flag:
                    code = mh.EPOCH_END
            code = mh.sync_epoch_code(code)
            if code == mh.STOP:
                self.shutdown_flag = True
                return None
            if code == mh.EPOCH_END:
                return batch_cnt, metric_acc
            # committed to one more global step: block until this
            # process's shard is ready (peers are already waiting in
            # the collective; a dead feed here stalls the job until
            # the distributed runtime's heartbeat fails it)
            batch = self._next_multihost_batch()
            metric_acc.append(self._do_update(batch))
            batch_cnt += 1

    def train(self):
        if self.optimizer is None:  # non-parametric model
            time.sleep(0.1)
            return self.model

        if self.multihost:
            result = self._epoch_loop_multihost()
        elif self.anakin is not None:
            result = self._epoch_loop_anakin()
        elif self.device_replay is not None:
            result = self._epoch_loop_device()
        else:
            result = self._epoch_loop_local()
        if result is None:
            return None
        # the epoch boundary: from the loop's return to train()'s, the
        # stretch in which this thread dispatches no step
        with telemetry.trace_span("trainer.boundary"):
            batch_cnt, metrics = result
            return self._finish_epoch(batch_cnt, self._drain(metrics))

    def _drain(self, metrics):
        """The epoch's metric sums on the host, by ONE ``device_get``
        once every step still queued has run (the ledger waits them
        out poll by poll, so that it knows to half a millisecond when
        the device ran dry; with telemetry off the ``device_get``
        itself does the waiting).  The fused replay
        step carried them on the device (``staging.epoch_sums``): a
        dozen scalars whatever the epoch's length.  The other loops'
        steps are other programs and hand over a list of per-step
        dicts of device scalars: fetched and released one by one,
        then summed here to the same form."""
        per_step = isinstance(metrics, list)
        with telemetry.trace_span("boundary.drain") as span:
            span.attrs["arrays"] = len(jax.tree.leaves(metrics))
            self.inflight.drain("boundary.drain")
            sums = jax.device_get(metrics)
            if per_step:
                # releasing the device scalars is part of the drain,
                # not of whoever drops the list last
                del metrics[:]
                sums = _sum_steps(sums)
            span.attrs["steps"] = int(sums["steps"])
        return sums

    def _finish_epoch(self, batch_cnt, sums):
        data_cnt = float(sums["dcnt"])
        loss_sum = {k: float(sums[k])
                    for k in ("p", "v", "r", "ent", "total") if k in sums}

        print("loss = %s" % " ".join(
            [k + ":" + "%.3f" % (l / data_cnt) for k, l in loss_sum.items()]))
        prof = self.timers.snapshot()
        flight = self.inflight.epoch()   # boundary to boundary, as prof
        if prof:
            # batch_wait = feed starvation; update = the dispatch and
            # the wait on the runtime's full queue
            print("profile = %s" % self.timers.format(prof))

        self.data_cnt_ema = (
            self.data_cnt_ema * 0.8 + data_cnt / (1e-2 + batch_cnt) * 0.2)
        lr = self.default_lr * self.data_cnt_ema / (1 + self.steps * 1e-5)
        self.opt_state = set_learning_rate(self.opt_state, lr)

        # snapshot: device -> host once per epoch (trainer thread owns
        # the device buffers, so saving here cannot race a donation).
        # _to_host is a collective for cross-process-sharded state, so
        # every process computes both copies, not just process 0.
        snapshot = TPUModel(self.model.module)
        with telemetry.trace_span("boundary.snapshot"):
            snapshot.params = self._to_host(self.params)
            host_opt = self._to_host(self.opt_state) if self.multihost \
                else None
            # _to_host is a collective for cross-process-sharded leaves,
            # so the target copy must also be fetched by EVERY process
            # here, not inside the primary-only save below
            host_tgt = (self._to_host(self.target_params)
                        if self.multihost
                        and self.target_params is not None
                        else None)
        self.last_metrics = {k: l / data_cnt for k, l in loss_sum.items()}
        for name, v in prof.items():
            self.last_metrics[f"profile_{name}_sec"] = v["sec"]
        # pipeline telemetry, canonical keys (docs/observability.md):
        # seconds the hot loop starved for its feed, seconds in which
        # the device had a step in flight (since the last boundary's
        # drain; the ledger's account), and the feed backlog at the
        # epoch boundary.  Always present — the device-replay path
        # simply has no batch wait (its draw rides the fused step), and
        # with telemetry off the ledger holds nothing: device_step_sec
        # is then the update section's seconds, the dispatch and the
        # wait on the runtime's queue, as it was before the ledger
        self.last_metrics["batch_wait_sec"] = \
            prof.get("batch_wait", {}).get("sec", 0.0)
        in_flight = flight.pop("in_flight_sec")
        self.last_metrics["device_step_sec"] = (
            in_flight if in_flight is not None
            else prof.get("update", {}).get("sec", 0.0))
        self.last_metrics.update(flight)   # starved_sec, run_ahead_p50
        self.last_metrics["queue_depth"] = self._queue_depth()
        # roofline/MFU keys (telemetry.costmodel): the harvested step
        # program's flops over this epoch's device-step seconds,
        # against the device's peak table (or the perf.* overrides).
        # Always present — None (JSON null) when the device kind is
        # unknown and no override is set, so the schema stays stable
        self.last_metrics.update(self.costmodel.epoch_metrics(
            self._step_label,
            self.last_metrics["device_step_sec"], batch_cnt))
        # guard counters (see analysis.guards): the compile count is
        # cumulative and must stay flat after the first epoch; host
        # transfers are the per-epoch delta and must not grow with
        # the step count
        self.last_metrics["retrace_count"] = self.retrace_guard.compiles
        self.last_metrics["targets_scan"] = self.targets_scan
        if self.transfer_guard is not None:
            self.last_metrics["host_transfers"] = \
                self.transfer_guard.snapshot()
        if self.shard_guard is not None:
            # per-epoch resharding copies at the update-step boundary;
            # steady state is 0 (donated state keeps its layout, the
            # feed stages batches onto the batch sharding)
            self.last_metrics["resharding_copies"] = \
                self.shard_guard.snapshot()
        if self.num_guard is not None:
            # the steps' in-graph finiteness flags rode the epoch's
            # sums to the ONE device_get of the drain — counting them
            # here costs no extra host syncs.  note_step raises
            # NumericsError when a max_nonfinite_steps budget is armed
            # and exceeded
            for _ in range(int(sums.get("nonfinite", 0))):
                self.num_guard.note_step(1.0)
            self.last_metrics.update(self.num_guard.snapshot())
        if self.device_replay is not None:
            self.last_metrics["replay_episodes"] = \
                self.device_replay.episodes_seen
            self.last_metrics["replay_dropped"] = \
                self.device_replay.dropped
        if self.anakin is not None:
            # fused-rollout production this epoch (committed env
            # transitions / completed games); the learner divides by
            # epoch wall time into anakin_{frames,games}_per_sec
            frames = float(sums["anakin_frames"])
            games = float(sums["anakin_games"])
            self.anakin_frames_total += frames
            self.anakin_games_total += games
            self.last_metrics["anakin_frames"] = int(frames)
            self.last_metrics["anakin_games"] = int(games)
        # off-policy robustness telemetry (docs/observability.md):
        # is_clip_frac is the mean fraction of acting steps whose
        # importance ratio hit the clip this epoch (standard: rho >
        # rho_clip; impact: the surrogate ratio outside 1 +- eps) —
        # the live measure of how off-policy the consumed data was
        if "clip_frac" in sums:
            self.last_metrics["is_clip_frac"] = round(
                float(sums["clip_frac"]) / int(sums["steps"]), 4)
        if self.target_params is not None:
            # steps since the target net last synced (hard interval),
            # or the Polyak EMA's effective horizon (constant by
            # construction) — plotted next to the rejection counter
            interval = int(
                self.args.get("target_update_interval", 0) or 0)
            tau = float(self.args.get("target_update_tau", 0.0) or 0.0)
            if tau > 0.0:
                age = round(1.0 / tau, 1)
            elif interval > 0:
                age = self.steps % interval
            else:
                age = self.steps  # frozen target: age = run length
            self.last_metrics["target_net_age"] = age
        if self.anakin is not None and self.anakin.K > 0:
            # epoch boundary: the newest snapshot joins the vectorized
            # opponent axis (oldest falls off) — scenario diversity as
            # one device-side shift instead of a league scheduler
            self.anakin_pool = self.anakin.refresh_pool(
                self.anakin_pool, self.params)
        self.epoch += 1
        if self.primary:  # process 0 owns the (shared) checkpoint dir
            with telemetry.trace_span("boundary.checkpoint"):
                try:
                    os.makedirs(_models_dir(), exist_ok=True)
                    self.save_train_state(self.epoch, host_opt, host_tgt)
                except OSError:
                    pass
        return snapshot

    def _queue_depth(self):
        """Feed backlog at the epoch boundary: device-staged batches +
        assembled host batches waiting (host path), or episodes queued
        for ring ingest (device replay).  A depth pinned at 0 alongside
        a large `batch_wait_sec` says the FEED is the bottleneck; a
        full queue with near-zero wait says the device is."""
        depth = 0
        if self.prefetcher is not None:
            depth += self.prefetcher.staged.qsize()
        if self.batcher is not None:
            depth += self.batcher.executor.output_queue.qsize()
        if self.device_replay is not None:
            depth += len(self.device_replay.pending)
        return depth

    def request_shutdown(self):
        """Ask the training thread to stop (checked between batches and
        broadcast to peers at the next control sync in multihost mode).

        The profiler trace is NOT closed here: ``trace`` belongs to the
        training thread (tick() runs there), so close() happens in
        ``run``'s finally block to avoid racing a tick mid-start."""
        self.shutdown_flag = True

    def stop_feeds(self):
        """Tear down the batch pipeline.  Call AFTER the training
        thread has exited: a multihost step the control collective
        already committed to still needs its batch, and starving it
        would stall every peer process in the collective."""
        if self.prefetcher is not None:
            self.prefetcher.stop()
        if self.batcher is not None:
            self.batcher.shutdown()

    def shutdown(self):
        self.request_shutdown()
        self.stop_feeds()

    def run(self):
        print("waiting training")
        self._run_thread = threading.current_thread()
        if self.transfer_guard is not None:
            # armed for the trainer's whole life: transfer counts are
            # reported per epoch from train() via snapshot()
            self.transfer_guard.__enter__()
        try:
            # warmup wait lives inside try so the finally block owns
            # trace.close() on every exit path, including warmup-abort
            if self.anakin is not None:
                # generation is on-device: there is no intake backlog
                # to warm — the first fused step makes its own data
                print("started training")
            elif self.device_replay is not None:
                # warm the ring itself: episodes stream into HBM as
                # they arrive, so training starts with a full ring.
                # A ring smaller than minimum_episodes (explicit config
                # or the byte clamp) must still start once it is full.
                replay = self.device_replay
                while replay.size < self.args["minimum_episodes"]:
                    if self.shutdown_flag:
                        return
                    self._maybe_emergency_save()
                    replay.ingest()
                    if replay.size and replay.size >= replay.capacity:
                        print(f"device replay ring ({replay.capacity})"
                              f" is smaller than minimum_episodes "
                              f"({self.args['minimum_episodes']}): "
                              f"starting with a full ring")
                        break
                    time.sleep(0.05)
                print("started training")
            else:
                while len(self.episodes) < self.args["minimum_episodes"]:
                    if self.shutdown_flag:
                        return
                    self._maybe_emergency_save()
                    time.sleep(1)
                if self.optimizer is not None:
                    self.batcher.run()
                    self.prefetcher = DevicePrefetcher(
                        self.batcher.batch,
                        depth=self.args.get("prefetch_batches", 2),
                        sharding=self.batch_sharding,
                        threads=self.args.get("transfer_threads", 2),
                        obs_float=self.compute_dtype,
                    )
                    print("started training")
            while not self.shutdown_flag:
                model = self.train()
                if model is None:
                    break
                self.update_flag = False
                with telemetry.trace_span("trainer.handoff"):
                    while not self.shutdown_flag:
                        # a SIGTERM can land while the learner thread
                        # is busy (it will never drain this queue
                        # mid-handler)
                        self._maybe_emergency_save()
                        try:
                            self.update_queue.put(
                                (model, self.steps), timeout=0.3)
                            break
                        except queue.Full:
                            continue
        except Exception as exc:
            # record before dying so Learner.update() can't deadlock
            # waiting on a snapshot this thread will never produce
            import traceback

            traceback.print_exc()
            self.failure = exc
            # the flight recorder's crash trigger, strictly AFTER the
            # failure is recorded: a dump that itself dies must not
            # leave Learner.update() waiting forever on this thread
            try:
                telemetry.crash_dump("trainer", exc)
            except Exception:
                pass
        finally:
            if self.transfer_guard is not None:
                self.transfer_guard.__exit__(None, None, None)
            self.trace.close()  # this thread owns the profiler trace
            self._run_thread = None


class RunningScore:
    """Streaming count/mean/std accumulator for outcome streams."""

    __slots__ = ("n", "total", "total_sq")

    def __init__(self):
        self.n = 0
        self.total = 0.0
        self.total_sq = 0.0

    def add(self, x):
        self.n += 1
        self.total += x
        self.total_sq += x * x

    @property
    def mean(self):
        return self.total / (self.n + 1e-6)

    @property
    def std(self):
        return max(0.0, self.total_sq / (self.n + 1e-6)
                   - self.mean ** 2) ** 0.5

    @property
    def win_rate(self):
        """Outcome in [-1, 1] mapped to a win probability."""
        return (self.mean + 1) / 2


class ReplayBuffer:
    """Episode deque shared with the Trainer, trimmed to the configured
    cap — or tighter under host-RAM pressure."""

    def __init__(self, episodes, maximum_episodes):
        self.episodes = episodes  # the Trainer's deque (shared)
        self.maximum_episodes = maximum_episodes
        self.warned = False

    def extend(self, episodes):
        self.episodes.extend(episodes)
        self._trim()

    def _cap(self):
        mem_percent = psutil.virtual_memory().percent
        if mem_percent <= 95:
            return self.maximum_episodes
        if not self.warned:
            import warnings

            warnings.warn(
                "memory usage %.1f%% with buffer size %d"
                % (mem_percent, len(self.episodes)))
            self.warned = True
        return int(len(self.episodes) * 95 / mem_percent)

    def _trim(self):
        cap = self._cap()
        while len(self.episodes) > cap:
            self.episodes.popleft()


class Learner:
    """Central conductor: owns the replay buffer, serves worker
    requests, reports stats, and checkpoints every epoch."""

    # class-level defaults so partially-constructed learners (tests
    # drive single subsystems via Learner.__new__) keep working: a real
    # __init__ overrides all of these
    worker = None
    trainer = None
    max_policy_lag = 0
    episodes_rejected_stale = 0
    _rejected_epoch = 0
    wal = None
    manifest = None
    episodes_replayed = 0
    checkpoint_checksum = True
    _kill_switch = None
    _resume = None
    infer_service = None
    _infer_respawns = 0
    _infer_respawn_at = 0.0
    _infer_disabled = False
    _infer_kill_epoch = 0
    _infer_killed = False
    # network serving tier (handyrl_tpu.serving): the SLO-bound
    # frontend feeding remote inference requests into the pipeline
    # batching window; supervised like the inference service (backoff
    # respawn + FailureWindow breaker in _serving_tick)
    serve_frontend = None
    _serve_respawns = 0
    _serve_respawn_at = 0.0
    _serve_disabled = False
    # replica-pool router (handyrl_tpu.serving.router): the one
    # endpoint over every registered serving replica, hosted by the
    # primary when router.mode is on; supervised like the frontend
    router_frontend = None
    _router_respawns = 0
    _router_respawn_at = 0.0
    _router_disabled = False
    # registry announcer: this replica's register/heartbeat loop into
    # a pool router (the local one, or serving.router_address)
    serve_announcer = None
    _serve_kill_epoch = 0
    _serve_killed = False
    # shm-vs-spill episode accounting (pipelined dataflow): cumulative
    # and per-epoch counts of episodes that rode the trajectory rings
    # vs episodes stamped ``shm_spilled`` (surge-hold overflow / full
    # rings) arriving on the control plane — together they reconcile
    # against episodes_received, the zero-loss proof
    episodes_shm = 0
    episodes_spilled = 0
    _shm_epoch = 0
    _spilled_epoch = 0
    _upload_backlog_epoch = 0   # deepest this epoch (metrics record)
    _upload_backlog_peak = 0    # deepest this run (status endpoint)

    def __init__(self, args, net=None, remote=False):
        from .config import Config

        cfg = args if isinstance(args, Config) else Config.from_dict(args)
        train_args = cfg.train_args.to_dict()
        env_args = dict(cfg.env_args)
        train_args["env"] = env_args
        self.args = train_args
        random.seed(self.args["seed"])

        # telemetry first: spans recorded by anything constructed below
        # (trainer warmup, worker bring-up) land in this run's log
        telemetry.configure_from_args(
            self.args, role="learner",
            primary=jax.process_index() == 0,
            # this process has JAX: its live spans also lie on the
            # profiler's clock, as hrl:<name> (children get none)
            annotate=jax.profiler.TraceAnnotation)
        # SIGTERM = preemption notice: durable state first (emergency
        # checkpoint + WAL seal inside the grace window), THEN the
        # flight-recorder dump and exit
        telemetry.install_signal_dump(pre_dump=self._preempt_save)
        # per-epoch self-time attribution over the span ring; the last
        # snapshot rides every flight-recorder dump so a crash leaves
        # its time-attribution next to its timeline
        self.attributor = telemetry.Attributor()
        telemetry.register_dump_extra(
            "attribution", lambda: self.attributor.last)
        self._run_t0 = time.monotonic()
        self._epoch_t = self._run_t0
        self._policy_lags = []        # episode lags consumed this epoch
        self._last_record = None      # latest metrics record (status)
        # lag-aware admission: with max_policy_lag > 0, an episode
        # whose generating snapshot is more than that many epochs
        # behind is DROPPED at intake (counted, never trained on) —
        # the budget that lets deep queues and bursty fleets run
        # without silently poisoning the replay buffer
        self.max_policy_lag = int(
            self.args.get("max_policy_lag", 0) or 0)
        self.episodes_rejected_stale = 0   # cumulative
        self._rejected_epoch = 0           # this epoch's count

        self.env = make_env(env_args)
        # guarantee at least ~update_episodes^0.85 eval games per epoch
        # (single source of truth: TrainConfig.effective_eval_rate)
        self.eval_rate = cfg.train_args.effective_eval_rate
        self.shutdown_flag = False
        # multi-host: every process runs a full learner (own actors,
        # own replay, own shard of every global batch); process 0
        # additionally owns checkpoints, metrics, and epoch decisions
        self.multihost = jax.process_count() > 1
        self.primary = jax.process_index() == 0

        # durability: resolve restart_epoch ("auto" or an explicit
        # epoch whose file may be corrupt) against the checkpoint
        # manifest BEFORE anything reads it — downstream consumers
        # (trainer restore, worker merged args) see the resolved int
        self.manifest = CheckpointManifest(_models_dir())
        self.checkpoint_checksum = bool(
            self.args.get("checkpoint_checksum", True))
        self._resume = resolve_restart(
            _models_dir(), self.args.get("restart_epoch", 0))
        self.args["restart_epoch"] = self._resume.epoch
        # the manifest-recorded digest of the train state that PAIRS
        # with the resumed params (runtime key, not config): the
        # trainer's restore proves the single train_state.ckpt on
        # disk is that exact file before trusting it — an epoch tag
        # alone cannot, because an emergency save reuses its epoch
        self.args["_resume_state_digest"] = \
            self._resume.train_state_digest

        self.model_epoch = self.args["restart_epoch"]
        self.model = self._initial_model(net)

        # per-model-id outcome streams
        self.generation_stats = {}
        self.league_stats = {}         # past epoch -> its outcomes as
        #                                a scheduled league opponent
        self.eval_stats = {}           # model_id -> RunningScore
        self.eval_stats_by_opponent = {}  # model_id -> {name: RunningScore}
        self.eval_stats_by_seat = {}   # model_id -> {seat: RunningScore}
        self.jobs_generated = 0
        self.jobs_evaluated = 0
        self.episodes_received = 0

        self.worker = WorkerServer(self.args) if remote \
            else WorkerCluster(self.args)
        # fleet health: every control-plane message timestamps its
        # peer; silence past heartbeat_timeout is a counted miss and
        # an eviction (respawn) for supervised local gathers
        self.fleet = FleetRegistry(
            heartbeat_timeout=float(
                self.args.get("heartbeat_timeout", 30.0) or 30.0))
        self._last_sweep = 0.0
        self.trainer = Trainer(self.args, self.model)
        self.trainer.manifest = self.manifest if self.primary else None
        # anakin epoch cadence: generation is on-device, so nothing
        # ticks episodes_received — epochs ride the trainer's own step
        # count instead (updates_per_epoch steps per epoch, config-
        # validated > 0 whenever anakin is configured)
        self._anakin_epoch_at = (
            self.trainer.steps
            + int(self.args.get("updates_per_epoch", 0) or 0))
        self.replay = ReplayBuffer(
            self.trainer.episodes, self.args["maximum_episodes"])
        self.metrics_path = self.args.get("metrics_path") or ""
        # episode WAL: admitted episodes are logged at intake so a
        # restarted learner replays its staged backlog instead of
        # re-generating it (durability.EpisodeWAL); primary only — the
        # WAL lives in the checkpoint dir this process owns
        self.wal = None
        self.episodes_replayed = 0
        self._wal_seen = set()
        if self.args.get("wal_enabled", True) and self.primary:
            self.wal = EpisodeWAL(
                os.path.join(_models_dir(), "wal"),
                segment_bytes=int(
                    self.args.get("wal_segment_mb", 8) or 8) << 20,
                flush_interval=float(
                    self.args.get("wal_flush_interval", 1.0)))
            if self._resume.epoch > 0:
                self._replay_wal()
        # durability chaos: a scheduled SIGKILL of this process
        # mid-epoch (the preemption drill the layer above must absorb)
        from .resilience import ChaosConfig, LearnerKillSwitch

        chaos_cfg = ChaosConfig.from_config(self.args.get("chaos") or {})
        self._kill_switch = None
        if chaos_cfg.learner_kill_enabled:
            self._kill_switch = LearnerKillSwitch(
                chaos_cfg,
                os.path.join(_models_dir(), "chaos_learner_killed"))
        # pipelined rollout dataflow (handyrl_tpu.pipeline): the
        # batched inference service answers every local worker's
        # per-step forward and receives finished trajectories over the
        # shm transport.  One service per learner PROCESS (each
        # multi-host replica serves its own workers); remote mode has
        # no service — shared memory does not cross machines, so
        # remote handshakes are refused and those workers keep local
        # inference.  Service death is a supervised fault: the server
        # loop respawns it behind the same backoff + windowed breaker
        # the actor fleet uses, and workers bridge the gap on their
        # local fallback path
        from .pipeline import InferenceService, PipelineConfig

        self._pipeline_cfg = PipelineConfig.from_config(
            self.args.get("pipeline") or {})
        # (the off/zero states ride the class-level defaults above,
        # the same pattern as _kill_switch/_resume)
        self._infer_kill_epoch = chaos_cfg.infer_kill_epoch
        self._serve_kill_epoch = chaos_cfg.serve_kill_epoch
        if self._pipeline_cfg.enabled and not remote:
            from .resilience.supervisor import FailureWindow

            self._infer_window = FailureWindow(
                int(self.args.get("max_respawns", 5)), 60.0)
            # GSPMD inference (ROADMAP item 2): the dispatch inherits
            # the TRAINING mesh, so one sharded program serves every
            # actor and network client with params on the learner's
            # tp/fsdp layout.  Multi-host replicas keep the unsharded
            # dispatch: each replica's service answers only its own
            # local workers, and a jit over the global mesh would need
            # every process in each forward (pod-scale inference rides
            # ROADMAP item 5's multihost work)
            infer_mesh = None
            if (self._pipeline_cfg.infer_mesh == "auto"
                    and not self.multihost):
                infer_mesh = self.trainer.train_mesh
            self.infer_service = InferenceService(
                self.model, self._pipeline_cfg,
                epoch=self.model_epoch, chaos=chaos_cfg,
                mesh=infer_mesh, fsdp=self.trainer.train_fsdp,
                max_reshard=int(
                    self.args.get("max_resharding_copies", 0) or 0))
            # the inference guard shares the trainer's cost model: its
            # forward program lands in the same registry under its own
            # label.  Attached on the guard (which respawn() reuses),
            # so the hook survives chaos-drill service respawns.  The
            # ASYNC hook: a blocking AOT compile in the batching
            # thread stalls replies past the workers' timeout and they
            # degrade to local inference for good
            self.infer_service.retrace_guard.on_compile = \
                self.trainer.costmodel.on_compile_async
            self.infer_service.start()
        # network serving tier (handyrl_tpu.serving): a framed TCP
        # frontend whose remote requests join the inference service's
        # batching window — one jitted dispatch covers the network and
        # shm planes.  Primary-local only: the frontend needs the
        # service, and a multihost replica's port would shadow the
        # primary's.  Death is a supervised fault (_serving_tick)
        from .serving import ServingConfig

        self._serving_cfg = ServingConfig.from_config(
            self.args.get("serving") or {})
        if self._serving_cfg.enabled:
            if self.infer_service is None or not self.primary:
                print("WARNING: serving.mode is on but the batched "
                      "inference service is not running here (pipeline "
                      "off, remote learner, or non-primary replica); "
                      "network serving disabled for this process")
            else:
                from collections import OrderedDict

                from .resilience.supervisor import FailureWindow
                from .serving import ServingFrontend

                self._serve_window = FailureWindow(
                    int(self.args.get("max_respawns", 5)), 60.0)
                self._serving_snapshots = OrderedDict()
                # multi-model routing: epoch-pinned network requests
                # resolve to the exact committed snapshot they asked
                # for instead of an error or the live model
                self.infer_service.model_resolver = \
                    self._resolve_serving_snapshot
                self.serve_frontend = ServingFrontend(
                    self.infer_service, self.env, self._serving_cfg,
                    max_frame_bytes=int(
                        self.args.get("max_frame_bytes", 0) or 0))
                self.serve_frontend.start()
        # replica-pool router (docs/serving.md "Pool routing"): the
        # primary can host the one-endpoint router over every
        # registered serving replica; death is a supervised fault
        # (_router_tick, the _serving_tick ladder)
        from .serving import RouterConfig

        self._router_cfg = RouterConfig.from_config(
            self.args.get("router") or {})
        if (self._router_cfg.enabled and self.primary
                and self.serve_frontend is not None):
            from .resilience.supervisor import FailureWindow
            from .serving import RouterFrontend

            self._router_window = FailureWindow(
                int(self.args.get("max_respawns", 5)), 60.0)
            self.router_frontend = RouterFrontend(
                self._router_cfg,
                max_frame_bytes=int(
                    self.args.get("max_frame_bytes", 0) or 0))
            self.router_frontend.start()
        # registry announcer: every serving frontend heartbeats its
        # advert into a pool router — a remote serving.router_address,
        # or the local router above (its own frontend registers like
        # any remote one, so single-host runs exercise the pool path)
        if self.serve_frontend is not None:
            target = None
            if self._serving_cfg.router_address:
                host, _, port = \
                    self._serving_cfg.router_address.rpartition(":")
                target = (host, int(port))
            elif self.router_frontend is not None:
                target = ("127.0.0.1", self.router_frontend.port)
            if target is not None:
                from .serving import ReplicaAnnouncer

                self.serve_announcer = ReplicaAnnouncer(
                    target[0], target[1],
                    f"learner-{jax.process_index()}-{os.getpid()}",
                    self._serving_advert,
                    interval=self._router_cfg.heartbeat_interval,
                    max_frame_bytes=int(
                        self.args.get("max_frame_bytes", 0) or 0))
                self.serve_announcer.start()
        # stall watchdog: the server loop and the communicator's
        # reader/writer threads beat once per pass; a loop silent past
        # max_stall_seconds is a counted stall_event with a stack dump
        # (the runtime twin of commlint's unbounded-recv rule)
        self.stall_watchdog = None
        if self.args.get("stall_watchdog", True):
            self.stall_watchdog = StallWatchdog(
                max_stall_seconds=float(
                    self.args.get("max_stall_seconds", 60.0) or 60.0))
            self.worker.liveness_hook = self.stall_watchdog.beat
            # the epoch boundary waits inside trainer.update(); beating
            # there keeps a LONG epoch distinct from a wedged server
            self.trainer.stall_beat = self.stall_watchdog.beat
            # a stall is the flight recorder's marquee trigger: the
            # ring turns the watchdog's stack dump into the causal
            # timeline of the 30s before the wedge
            self.stall_watchdog.on_stall = telemetry.stall_hook
            self.stall_watchdog.start()
        # lock-order/contention guard: wraps every control-plane lock
        # in a timing proxy; per-epoch lock_contention_sec and
        # lock_order_inversions land in metrics.jsonl next to
        # stall_events (the runtime twin of racelint's
        # lock-order-cycle rule).  arm() is tolerant of absent
        # subsystems, so one list covers every configuration
        self.lock_guard = None
        if self.args.get("lock_order_guard", True):
            self.lock_guard = LockOrderGuard()
            for obj, attr in (
                    (self.worker, "_lock"),
                    (self.worker, "_admit_lock"),
                    (getattr(self.worker, "supervisor", None), "_lock"),
                    (self.fleet, "_lock"),
                    (self.infer_service, "_lock"),
                    (self.serve_frontend, "_lock"),
                    (self.router_frontend, "_lock"),
                    (self.stall_watchdog, "_lock"),
            ):
                self.lock_guard.arm(obj, attr)
        # per-epoch resource-population sampling (fd/thread/shm
        # counts + growth vs the post-warmup baseline) — the runtime
        # twin of leaklint's lifecycle rules.  max_fd_growth > 0
        # makes the budget a hard ResourceError
        self.resource_ledger = None
        if self.args.get("resource_ledger", True):
            self.resource_ledger = ResourceLedger(
                max_fd_growth=int(
                    self.args.get("max_fd_growth", 0) or 0))
        # read-only live status endpoint (dashboards poll this instead
        # of touching the control plane); 0 = off
        self.status = None
        status_port = int(self.args.get("status_port", 0) or 0)
        if status_port and self.primary:
            from .telemetry.status import StatusServer

            # a router-hosting learner answers /healthz from the
            # registry snapshot (pool health, constant-time, no
            # per-replica dial); otherwise the constant liveness body
            healthz_fn = None
            if self.router_frontend is not None:
                healthz_fn = self.router_frontend.healthz
            self.status = StatusServer(status_port,
                                       self._status_snapshot,
                                       healthz_fn=healthz_fn)

    def _status_snapshot(self):
        """Live JSON for the status endpoint: fleet + telemetry + the
        latest per-epoch metrics record.  Read-only by construction."""
        snap = {
            "epoch": self.model_epoch,
            "episodes_received": self.episodes_received,
            "episodes_rejected_stale": self.episodes_rejected_stale,
            "episodes_replayed": self.episodes_replayed,
            "connections": self.worker.connection_count(),
            "time_sec": round(time.monotonic() - self._run_t0, 3),
            "fleet": self.fleet.snapshot(),
            "telemetry": telemetry.stats(),
            "last_record": self._last_record,
        }
        lock_guard = getattr(self, "lock_guard", None)
        if lock_guard is not None:
            snap["locks"] = lock_guard.stats()
        ledger = getattr(self, "resource_ledger", None)
        if ledger is not None:
            snap["resources"] = ledger.stats()
        if self.wal is not None:
            snap["wal"] = self.wal.stats()
        trainer = getattr(self, "trainer", None)
        costmodel = getattr(trainer, "costmodel", None)
        if costmodel is not None:
            # roofline accounting + the last epoch's self-time tree
            # (docs/observability.md "Attribution & roofline")
            perf = costmodel.stats()
            perf["attribution"] = self.attributor.last
            snap["perf"] = perf
        num_guard = getattr(trainer, "num_guard", None)
        if num_guard is not None:
            snap["numerics"] = num_guard.stats()
        if trainer is not None and \
                getattr(trainer, "anakin", None) is not None:
            snap["anakin"] = {
                "num_envs": trainer.anakin.num_envs,
                "unroll_length": trainer.anakin.unroll,
                "opponent_pool": trainer.anakin.K,
                "frames_total": int(trainer.anakin_frames_total),
                "games_total": int(trainer.anakin_games_total),
            }
        if self.infer_service is not None:
            snap["pipeline"] = {
                **self.infer_service.stats(),
                "respawns": self._infer_respawns,
                "episodes_shm": self.episodes_shm,
                "episodes_spilled": self.episodes_spilled,
                # run peak, not the per-epoch accumulator: every key
                # in this section is cumulative-monotone, so a
                # dashboard never sees a live backlog "vanish" at an
                # epoch boundary reset
                "upload_backlog_peak": self._upload_backlog_peak,
            }
        if self.serve_frontend is not None:
            snap["serving"] = {
                **self.serve_frontend.stats(),
                "respawns": self._serve_respawns,
            }
            if self.serve_announcer is not None:
                snap["serving"]["announcer"] = {
                    "alive": self.serve_announcer.alive,
                    "generation": self.serve_announcer.generation,
                    "registrations":
                        self.serve_announcer.registrations,
                }
        if self.router_frontend is not None:
            # pool routing (docs/serving.md "Pool routing"): router
            # counters + the registry snapshot (pool membership,
            # per-replica generation/age/advert)
            snap["router"] = {
                **self.router_frontend.stats(),
                "respawns": self._router_respawns,
            }
        return snap

    def _serving_advert(self):
        """This replica's registry advert (announcer callback, runs on
        the announcer thread): the frontend's capacity/load/p99 plus
        the committed epochs pinned requests can route here for — the
        manifest's entries, exactly what the serving resolver can load
        (digest verification happens at resolve time; the advert is a
        cheap bulletin, not a proof)."""
        epochs = {int(self.model_epoch)}
        if self.manifest is not None:
            try:
                epochs.update(
                    int(e) for e in self.manifest.load()["entries"])
            except (ValueError, TypeError, OSError):
                pass
        return self.serve_frontend.advert(epochs=epochs)

    # -- durability ---------------------------------------------------
    def _wal_keep_episodes(self):
        return (int(self.args.get("wal_keep_episodes", 0) or 0)
                or self.args["maximum_episodes"])

    def _replay_wal(self):
        """Restore the staged backlog from the episode WAL (resume
        path, before any thread starts).  Replayed episodes refill the
        replay store — device ring or host deque — but do NOT tick
        ``episodes_received``: epoch cadence tracks fresh arrivals,
        and the replayed window's epochs were already recorded by the
        previous incarnation.  The staleness budget still applies —
        resuming is not a license to train on hopeless data."""
        from collections import deque as _deque

        keep = self._wal_keep_episodes()
        with telemetry.trace_span("wal.replay"):
            restored = _deque(maxlen=keep)
            scanned = stale = 0
            for _seq, episode in self.wal.replay(self._wal_seen):
                scanned += 1
                if (self.max_policy_lag > 0
                        and self._episode_lag(episode)
                        > self.max_policy_lag):
                    stale += 1
                    continue
                restored.append(episode)
            restored = list(restored)
            if self.trainer.device_replay is not None:
                # straight into the ring on this (pre-trainer) thread
                self.episodes_replayed = \
                    self.trainer.device_replay.warm_start(restored)
            else:
                self.replay.extend(restored)
                self.episodes_replayed = len(restored)
        if scanned:
            print(f"wal: replayed {self.episodes_replayed} of "
                  f"{scanned} logged episode(s) into the backlog"
                  + (f" ({stale} past the staleness budget)"
                     if stale else ""))

    def _preempt_save(self):  # pragma: no cover - exercised by SIGTERM
        """SIGTERM pre-dump hook (telemetry.install_signal_dump):
        durable state inside the grace window, in rescue order — seal
        the WAL (cheap, this thread owns it), ask the trainer thread
        for an emergency checkpoint with a deadline, then tear the
        local fleet down so orphans don't fight the relaunch for
        cores.  Runs on the main (server) thread; everything here must
        bound its own wait."""
        print("SIGTERM: preemption grace window — sealing WAL and "
              "requesting an emergency checkpoint")
        if self.wal is not None:
            try:
                self.wal.seal()
            except Exception as exc:
                # broad on purpose: the signal can land mid-roll (file
                # just closed => ValueError, not OSError), and a failed
                # seal must cost the seal, never the emergency
                # checkpoint and fleet teardown behind it
                print(f"WARNING: WAL seal failed ({exc!r})")
        grace = float(self.args.get("preempt_grace_seconds", 5.0) or 0.0)
        trainer = getattr(self, "trainer", None)
        if (grace > 0 and trainer is not None and self.primary
                and not self.multihost):
            event = threading.Event()
            trainer.emergency = event
            if not event.wait(grace):
                print("WARNING: emergency checkpoint did not land "
                      f"inside the {grace:.1f}s grace window; resume "
                      "falls back to the last epoch boundary")
        if self.worker is not None:
            try:
                self.worker.terminate_fleet()
            except Exception as exc:  # teardown must not block the exit
                print(f"WARNING: fleet teardown failed ({exc!r})")

    def _initial_model(self, net):
        if net is not None:
            model = net if isinstance(net, TPUModel) else TPUModel(net)
        else:
            model = TPUModel(self.env.net())
        if model.params is None:
            self.env.reset()
            obs = self.env.observation(self.env.players()[0])
            model.init_params(obs, seed=self.args["seed"])
        if self.model_epoch > 0:
            # the resolved resume point names the exact file (an
            # emergency save resumes from latest.ckpt, not the epoch
            # file) and already verified it; read_verified re-checks at
            # load so a race with pruning fails loudly, not weirdly
            src = (self._resume.model_file
                   if self._resume is not None
                   and self._resume.model_file
                   else model_path(self.model_epoch))
            model.params = read_verified(src)["params"]
        return model

    # -- checkpointing ----------------------------------------------
    def _prune_checkpoints(self):
        """Retention: keep the newest ``checkpoint_keep_last`` epoch
        files plus every ``checkpoint_keep_every``-th epoch (0 = keep
        all) so week-long runs don't accumulate thousands of pickles.
        The reference keeps everything (train.py:448-455).  Incremental:
        only epochs newly crossing the retention boundary are removed
        (one catch-up sweep on the first update after a restart)."""
        keep_last = int(self.args.get("checkpoint_keep_last", 0) or 0)
        if keep_last <= 0:
            return
        keep_every = int(self.args.get("checkpoint_keep_every", 0) or 0)
        boundary = self.model_epoch - keep_last + 1  # prune below this
        removed = []
        for epoch in range(getattr(self, "_pruned_below", 1), boundary):
            if keep_every > 0 and epoch % keep_every == 0:
                continue
            try:
                os.remove(model_path(epoch))
            except OSError:
                pass  # already pruned (or an epoch that never saved)
            removed.append(epoch)
        self._pruned_below = max(getattr(self, "_pruned_below", 1),
                                 boundary)
        if removed and self.manifest is not None:
            # retention prunes the index too: a manifest entry whose
            # file is gone would just be noise in the fallback scan
            self.manifest.forget(removed)

    def update_model(self, model, steps):
        print("updated model(%d)" % steps)
        self.model_epoch += 1
        self.model = model
        # the chaos surge trigger runs on the learner's epoch clock
        # (no-op without an armed monkey; see WorkerCluster.note_epoch)
        if self.worker is not None:
            self.worker.note_epoch(self.model_epoch)
        if self.infer_service is not None:
            # hot-swap the serving snapshot BEFORE jobs labeled with
            # the new epoch go out: the service adopts it between
            # batches, so no in-flight request is dropped and workers'
            # epoch-pinned wrappers stay served across the boundary
            self.infer_service.set_model(model, self.model_epoch)
            if (self._infer_kill_epoch > 0 and not self._infer_killed
                    and self.model_epoch >= self._infer_kill_epoch):
                # pipeline chaos: the service dies without a parting
                # heartbeat — workers must bridge on local fallback
                # until the supervised respawn below brings it back
                self._infer_killed = True
                print(f"CHAOS: killing the inference service at epoch "
                      f"{self.model_epoch}")
                self.infer_service.inject_kill()
        if (self.serve_frontend is not None
                and self._serve_kill_epoch > 0 and not self._serve_killed
                and self.model_epoch >= self._serve_kill_epoch):
            # pool-routing chaos: this replica goes SILENT — frontend
            # and announcer die without a goodbye, so the router must
            # learn of the death from missing heartbeats (sweep
            # eviction) and re-route, pins included, to the survivors
            self._serve_killed = True
            print(f"CHAOS: killing the serving replica at epoch "
                  f"{self.model_epoch}")
            if self.serve_announcer is not None:
                self.serve_announcer.kill()
            self.serve_frontend.inject_kill()
        if not self.primary:
            # replicas serve the in-memory snapshot to their own
            # workers; only process 0 writes the checkpoint dir
            return
        os.makedirs(_models_dir(), exist_ok=True)
        state = {"params": model.params, "steps": steps,
                 "epoch": self.model_epoch}
        digest = write_atomic(model_path(self.model_epoch), state,
                              checksum=self.checkpoint_checksum)
        write_atomic(latest_model_path(), state,
                     checksum=self.checkpoint_checksum)
        # the manifest is the COMMIT POINT: the epoch exists (for
        # auto-resume and for fallback ordering) once this lands; the
        # trainer stamped the matching train-state digest just before
        if self.manifest is not None:
            self.manifest.commit(
                self.model_epoch, model_path(self.model_epoch),
                digest, steps,
                train_state_digest=self.trainer.last_state_digest)
        self._prune_checkpoints()
        if self.wal is not None:
            # checkpoint landed: the active WAL segment rolls (it is
            # now a sealed, retirable unit) and segments the buffer no
            # longer covers retire
            self.wal.checkpoint_landed(self._wal_keep_episodes())

    # -- episode / result intake ------------------------------------
    def _episode_lag(self, episode):
        """Policy-version lag of one arriving episode: learner epoch
        now minus the snapshot epoch that generated it."""
        gen = episode.get("gen_model_epoch")
        if gen is None:
            # pre-stamp episode (or a replayed fixture): fall back to
            # the scheduled trained-seat label
            job = episode["args"]
            labels = [job["model_id"][p] for p in job["player"]]
            gen = max([l for l in labels if l >= 0],
                      default=self.model_epoch)
        return max(0, self.model_epoch - gen)

    def _note_intake(self, episode, lag=None):
        """Per-episode telemetry at intake: the policy-version lag
        (the off-policy staleness signal reduced into `policy_lag_*`
        per epoch; precomputed by the admission loop when armed) and,
        for trace-stamped episodes, an intake event under the
        episode's own context so the exported trace crosses the
        worker -> learner process boundary."""
        if lag is None:
            lag = self._episode_lag(episode)
        self._policy_lags.append(lag)
        ctx = episode.get("trace")
        if ctx is not None and telemetry.enabled():
            prev = telemetry.current_trace()
            telemetry.set_trace(ctx)
            telemetry.add_event("episode.intake", lag=int(lag))
            telemetry.set_trace(prev)  # the rpc span keeps ITS context

    def feed_episodes(self, episodes):
        arrived = [e for e in episodes if e is not None]
        for episode in arrived:
            # shm-plane transport stamps, popped BEFORE the episode
            # can reach the WAL or the replay buffer: `shm_spilled`
            # marks a control-plane spill (full ring / surge-hold
            # overflow) and `upload_backlog` carries the worker-side
            # hold-backlog depth at ship time — both reduced into the
            # per-epoch brownout metrics
            if episode.pop("shm_spilled", False):
                self.episodes_spilled += 1
                self._spilled_epoch += 1
            backlog = episode.pop("upload_backlog", 0)
            if backlog > self._upload_backlog_epoch:
                self._upload_backlog_epoch = int(backlog)
            if backlog > self._upload_backlog_peak:
                self._upload_backlog_peak = int(backlog)
        if self.max_policy_lag > 0:
            # admission control: past-budget episodes are counted and
            # dropped BEFORE any stats/buffer touch them.  Rejected
            # episodes still tick the intake clock below — epoch
            # cadence tracks arrivals, so a stale flood cannot stall
            # the epoch counter while it is being shed.  The lag
            # computed here is reused by _note_intake below
            admitted = []
            for episode in arrived:
                lag = self._episode_lag(episode)
                if lag > self.max_policy_lag:
                    self.episodes_rejected_stale += 1
                    self._rejected_epoch += 1
                else:
                    admitted.append((episode, lag))
        else:
            admitted = [(episode, None) for episode in arrived]
        kept = [episode for episode, _ in admitted]
        if self.wal is not None and kept:
            # write-ahead: an admitted episode reaches the log before
            # any stats or buffer touch it, so a crash between here
            # and the next checkpoint cannot lose the backlog
            for episode in kept:
                self.wal.append(episode)
        for episode, lag in admitted:
            self._note_intake(episode, lag)
            job = episode["args"]
            # trained seats credit the epoch that actually finished the
            # episode (the pool may swap snapshots mid-flight; see
            # RolloutPool); opponent seats keep their scheduled label
            final = episode.get("final_model_epoch")
            for p in job["player"]:
                label = job["model_id"][p]
                if final is not None and label >= 0:
                    label = final
                stats = self.generation_stats.setdefault(
                    label, RunningScore())
                stats.add(episode["outcome"][p])
            # league seats (scheduled past-self opponents) track
            # SEPARATELY, keyed by the snapshot epoch they played:
            # folding them into generation_stats would collide with
            # the label that epoch earned when it was the one training
            for p, label in job["model_id"].items():
                if label >= 0 and p not in job["player"]:
                    self.league_stats.setdefault(
                        label, RunningScore()).add(episode["outcome"][p])
        before = self.episodes_received
        self.episodes_received += len(arrived)
        for mark in range(before // 100 + 1,
                          self.episodes_received // 100 + 1):
            print(mark * 100, end=" ", flush=True)
        if self.trainer.device_replay is not None:
            # HBM ring is the only replay store: retaining a second
            # full copy in the host deque would double replay memory
            # for a buffer nothing reads
            self.trainer.device_replay.offer(kept)
        else:
            self.replay.extend(kept)
        if self._kill_switch is not None:
            # durability chaos: the scheduled learner SIGKILL ticks on
            # the intake clock (deterministically mid-window)
            self._kill_switch.note(self.model_epoch,
                                   self.episodes_received)

    def feed_results(self, results):
        for result in results:
            if result is None:
                continue
            job, opponent = result["args"], result["opponent"]
            players = self.env.players()
            for p in job["player"]:
                model_id = job["model_id"][p]
                score = result["result"][p]
                self.eval_stats.setdefault(model_id, RunningScore()
                                           ).add(score)
                by_opp = self.eval_stats_by_opponent.setdefault(model_id, {})
                by_opp.setdefault(opponent, RunningScore()).add(score)
                # per-seat streams surface play-order asymmetries
                # (e.g. a strong first seat masking a weak second)
                by_seat = self.eval_stats_by_seat.setdefault(model_id, {})
                by_seat.setdefault(
                    players.index(p), RunningScore()).add(score)

    # -- epoch boundary ---------------------------------------------
    def _report_win_rates(self, record):
        """Print the epoch's eval summary (format is a public API: the
        plot scripts parse these prefixes)."""
        overall = self.eval_stats.get(self.model_epoch)
        if overall is None:
            print("win rate = Nan (0)")
            return

        def line(tag, score):
            label = " (%s)" % tag if tag else ""
            print("win rate%s = %.3f (%.1f / %d)"
                  % (label, score.win_rate,
                     (score.total + score.n) / 2, score.n))
            record["win_rate" + ("_" + tag if tag else "")] = score.win_rate

        by_opp = self.eval_stats_by_opponent.get(self.model_epoch, {})
        single_opponent = (
            len(self.args.get("eval", {}).get("opponent", [])) <= 1
            and len(by_opp) <= 1)
        if single_opponent:
            line("", overall)
        else:
            line("total", overall)
            for name in sorted(by_opp):
                line(name, by_opp[name])
        by_seat = self.eval_stats_by_seat.get(self.model_epoch, {})
        if len(by_seat) > 1:
            print("win rate by seat = " + " ".join(
                "%d:%.3f(%d)" % (s, by_seat[s].win_rate, by_seat[s].n)
                for s in sorted(by_seat)))
            for s, score in by_seat.items():
                record[f"win_rate_seat_{s}"] = score.win_rate

    def _report_generation(self, record):
        stats = self.generation_stats.get(self.model_epoch)
        if stats is None:
            print("generation stats = Nan (0)")
            return
        print("generation stats = %.3f +- %.3f" % (stats.mean, stats.std))
        record["generation_mean"] = stats.mean
        record["generation_std"] = stats.std
        if self.league_stats:
            # each past self's mean outcome while seated as a league
            # opponent (negative = the current model beats it)
            print("league stats = " + " ".join(
                "%d:%.3f(%d)" % (e, s.mean, s.n)
                for e, s in sorted(self.league_stats.items())))
            record["league_opponent_mean"] = {
                str(e): round(s.mean, 4)
                for e, s in self.league_stats.items()}

    def update(self):
        """One epoch boundary on the server thread, which takes no
        episode in meanwhile: the whole of it is ``learner.update``."""
        with telemetry.trace_span("learner.update"):
            self._update()
        telemetry.flush()              # epoch boundary: spans to disk

    def _update(self):
        print()
        print("epoch %d" % self.model_epoch)
        # NOTE the epoch field is stamped at epoch START (before
        # update_model increments it), so a run's records read
        # [restart_epoch, restart_epoch+1, ...] — docs/observability.md
        record = {"epoch": self.model_epoch}
        now = time.monotonic()
        record["time_sec"] = round(now - self._run_t0, 3)
        record["epoch_wall_sec"] = round(now - self._epoch_t, 3)
        self._epoch_t = now
        # off-policy staleness over the episodes consumed this epoch,
        # plus how many arrivals the staleness budget rejected
        record.update(telemetry.summarize_lags(self._policy_lags))
        self._policy_lags = []
        record["episodes_rejected_stale"] = self._rejected_epoch
        self._rejected_epoch = 0
        # durability telemetry: how many backlog episodes this run
        # restored from the WAL (constant after startup; > 0 proves a
        # resume re-entered a warm pipeline) and the log's live shape
        record["episodes_replayed"] = self.episodes_replayed
        if self.wal is not None:
            record.update(self.wal.stats())
        self._report_win_rates(record)
        self._report_generation(record)

        model, steps = self.trainer.update()
        if model is None:
            # keep serving the last snapshot, but say so LOUDLY: a run
            # that silently reports the initial net's win rate for
            # hours is worse than one that crashes (r4 lesson)
            if self.trainer.failure is not None:
                print("WARNING: trainer thread failed "
                      f"({self.trainer.failure!r}); serving the last "
                      "model unchanged")
            model = self.model
        self.update_model(model, steps)
        record["steps"] = steps
        record.update(getattr(self.trainer, "last_metrics", {}))
        if "anakin_frames" in record:
            # fused-rollout throughput (docs/observability.md):
            # committed env transitions / completed self-play games
            # per second of epoch wall time — the number the Anakin
            # path exists to move by orders of magnitude
            wall = record.get("epoch_wall_sec") or 0.0
            if wall > 0:
                record["anakin_frames_per_sec"] = round(
                    record["anakin_frames"] / wall, 1)
                record["anakin_games_per_sec"] = round(
                    record["anakin_games"] / wall, 1)
        record.update(self._fleet_record())
        if self.infer_service is not None:
            # pipelined-inference telemetry (docs/observability.md):
            # per-epoch batch-size distribution, mean batching-window
            # wait, cumulative ring-full backpressure, torn-slot
            # skips, and respawns
            record.update(self.infer_service.epoch_stats())
            record["infer_respawns"] = self._infer_respawns
            # shm-vs-spill episode accounting for this epoch plus the
            # deepest worker-side hold backlog observed at intake —
            # the brownout visibility triple (docs/observability.md):
            # shm + spilled episodes reconcile against arrivals, so
            # a surge hold is visible as spills and backlog, never as
            # silent episode loss
            record["episodes_shm"] = self._shm_epoch
            record["episodes_spilled"] = self._spilled_epoch
            record["upload_backlog"] = self._upload_backlog_epoch
            self._shm_epoch = 0
            self._spilled_epoch = 0
            self._upload_backlog_epoch = 0
        if self.serve_frontend is not None:
            # network serving telemetry (docs/observability.md):
            # per-epoch request/ok/shed/error counts, QPS, and the
            # log2-histogram latency reduction; serve_shed > 0 is the
            # admission-control drill's counted proof — sheds are
            # typed replies, never silent drops
            record.update(self.serve_frontend.epoch_stats())
            record["serve_respawns"] = self._serve_respawns
        if self.router_frontend is not None:
            # pool-routing telemetry (docs/observability.md):
            # router_pool_size / reroutes / pool_sheds join the
            # serve_* keys; the plot script reads them through the
            # series() skip-absent pattern, so pre-router metrics
            # files still render
            record.update(self.router_frontend.epoch_stats())
            record["router_respawns"] = self._router_respawns
        if self.stall_watchdog is not None:
            # control-plane wedges this epoch (server loop + reader/
            # writer threads silent past max_stall_seconds); steady
            # state is 0 — see analysis.guards.StallWatchdog
            record["stall_events"] = self.stall_watchdog.snapshot()
        if self.lock_guard is not None:
            # seconds threads spent waiting on control-plane locks +
            # runtime ABBA order inversions this epoch; steady state
            # is (~0, 0) — see analysis.guards.LockOrderGuard
            record.update(self.lock_guard.snapshot())
        if self.resource_ledger is not None:
            # fd/thread/shm population + growth over the post-warmup
            # baseline; a healthy fleet PLATEAUS after bring-up — see
            # analysis.guards.ResourceLedger
            record.update(self.resource_ledger.snapshot())
        # wall-time reconciliation (telemetry.attribution): the residual
        # is DEFINED over the record's own rounded values, so
        # epoch_wall_sec == sum(profile_*_sec) + untracked_residual_sec
        # holds exactly in every emitted record; slightly negative =
        # trainer-thread sections vs learner-thread wall window skew
        record["untracked_residual_sec"] = \
            telemetry.untracked_residual(record)
        # fold this epoch's span ring into the self-time tree (status
        # perf section + flight-recorder dumps); no-op telemetry-off
        self.attributor.note_epoch(record)
        if self.metrics_path and self.primary:
            with open(self.metrics_path, "a") as f:
                f.write(json.dumps(record) + "\n")
        self._last_record = record     # status endpoint reads this
        self.replay.warned = False

    # -- fleet health -----------------------------------------------
    def _fleet_record(self):
        """Per-epoch fleet metrics (fleet_size / respawns /
        heartbeat_misses / conn_drops), reported next to the guard
        counters in metrics.jsonl.  Degradation is LOUD but non-fatal:
        a shrunken fleet slows episode intake, it does not stop
        training."""
        self.fleet.record_drops(self.worker.drop_stats())
        snap = self.fleet.snapshot()
        stats = self.worker.fleet_stats()
        snap["respawns"] = stats.get("respawns", 0)
        # expected strength: the supervisor's slot count for local
        # fleets; for elastic remote fleets, the registry's sustained
        # peak (updated at sweep time, after dead-peer reconciliation)
        expected = stats.get("slots", self.fleet.peak_size)
        if snap["fleet_size"] < expected:
            print(f"WARNING: fleet degraded: {snap['fleet_size']} of "
                  f"{expected} gathers responsive "
                  f"({snap['respawns']} respawns, "
                  f"{stats.get('slots_dead', 0)} slots dead); "
                  "training continues on the surviving fleet")
        return snap

    def _sweep_fleet(self):
        """Time-gated heartbeat expiry: newly stale peers are reported
        to the communicator, which (for supervised local gathers)
        evicts the wedged child so the supervisor respawns it."""
        now = time.monotonic()
        if now - self._last_sweep < 1.0:
            return
        if self.wal is not None:
            # idle-tail fsync: appends flush themselves on cadence,
            # but buffered bytes from a quiet fleet must not sit
            # unsynced forever
            self.wal.maybe_flush(now)
        # the loop normally passes here every ~0.3-1s; a much larger
        # gap means THIS thread stalled (an epoch boundary inside
        # update(), checkpoint I/O) while peer messages queued unread
        stalled = self._last_sweep > 0.0 and now - self._last_sweep > 5.0
        self._last_sweep = now
        self._check_fleet_dead(now)
        # peers whose connection the communicator already dropped
        # (EOF/reset) are gone, not merely silent: forget them so
        # fleet_size tracks the live fleet, and heartbeat misses count
        # only wedged-but-connected peers
        live = set(self.worker.live_connections())
        for peer in self.fleet.peers():
            if peer not in live:
                self.fleet.forget(peer)
        if stalled:
            # the silence was ours, not the peers': refresh everyone
            # rather than mass-evicting a healthy fleet whose proof of
            # life is still sitting in the input queue
            self.fleet.pardon(now)
            return
        for conn in self.fleet.sweep(now):
            self.worker.report_stale(conn)

    def _check_fleet_dead(self, now):
        """Every supervised gather slot circuit-broke: nothing can
        ever rejoin a LOCAL fleet (no accept port), so a silent idle
        spin would hang the run forever — shut down cleanly instead.
        Multi-host replicas cannot unilaterally exit the collective,
        so they (and elastic remote servers, which lack a supervisor)
        only warn, loudly and repeatedly."""
        stats = self.worker.fleet_stats()
        slots = stats.get("slots", 0)
        if (not slots or stats.get("fleet_alive", 1) > 0
                or stats.get("slots_dead", 0) < slots
                or self.shutdown_flag):
            return
        if getattr(self.trainer, "anakin", None) is not None:
            # anakin: the fleet only evaluates — generation is on
            # device, so training continues; just lose the win-rate
            # stream LOUDLY instead of killing a healthy run
            if now - getattr(self, "_fleet_dead_warned", 0.0) > 30.0:
                self._fleet_dead_warned = now
                print("WARNING: the entire eval worker fleet is dead; "
                      "anakin training continues WITHOUT win-rate "
                      "evaluation")
        elif not self.multihost:
            print("ERROR: the entire local gather fleet is dead "
                  "(circuit breaker tripped on every slot); shutting "
                  "down — raise max_respawns or fix the crash in the "
                  "gather/worker logs")
            self.shutdown_flag = True
            self.worker.begin_drain()
            self.trainer.request_shutdown()
        elif now - getattr(self, "_fleet_dead_warned", 0.0) > 30.0:
            self._fleet_dead_warned = now
            print("WARNING: this process's entire gather fleet is "
                  "dead; training is starved of episodes")

    # -- pipelined dataflow ------------------------------------------
    def _on_shm(self, specs):
        """The shm handshake (verb ``"shm"``): allocate rings + a
        client slot per asking worker.  None refuses — pipeline off,
        remote learner (no shared memory across machines), shutdown,
        or a malformed spec — and the worker keeps local inference."""
        replies = []
        for spec in specs:
            if (self.infer_service is None or self._infer_disabled
                    or self.shutdown_flag or not isinstance(spec, dict)):
                replies.append(None)
                continue
            try:
                replies.append(self.infer_service.attach(spec))
            except Exception as exc:  # a bad spec costs that worker
                print(f"WARNING: shm attach failed ({exc!r}); "
                      "the peer keeps local inference")
                replies.append(None)
        return replies

    def _pipeline_tick(self):
        """Once per server-loop pass: drain the shm trajectory rings
        into episode intake, and supervise the service thread — a dead
        service respawns behind backoff and the fleet's windowed
        circuit breaker (workers bridge the gap on local fallback; a
        breaker trip disables the pipeline for the rest of the run
        instead of respawn-storming)."""
        svc = self.infer_service
        if svc is None:
            return
        episodes = svc.drain_trajectories(max_episodes=512)
        if episodes:
            self.episodes_shm += len(episodes)
            self._shm_epoch += len(episodes)
            with telemetry.trace_span("intake.shm",
                                      episodes=len(episodes)):
                self.feed_episodes(episodes)
        if svc.alive or self._infer_disabled or self.shutdown_flag:
            return
        now = time.monotonic()
        if self._infer_respawn_at == 0.0:
            if self._infer_window.record(now):
                self._infer_disabled = True
                print("ERROR: the inference service keeps dying "
                      "(circuit breaker tripped); pipelined inference "
                      "disabled for this run — workers continue on "
                      "local CPU inference")
                return
            delay = float(self.args.get("respawn_backoff", 0.5) or 0.5)
            self._infer_respawn_at = now + delay
            print(f"WARNING: inference service died; respawning in "
                  f"{delay:.1f}s (workers fall back to local "
                  f"inference meanwhile)")
        elif now >= self._infer_respawn_at:
            self._infer_respawn_at = 0.0
            self._infer_respawns += 1
            svc.set_model(self.model, self.model_epoch)
            svc.respawn()
            print("inference service respawned "
                  f"(incarnation {svc.board.generation})")

    # -- network serving tier ----------------------------------------
    def _resolve_serving_snapshot(self, epoch):
        """epoch -> model for the serving tier's multi-model routing
        (league/opponent-pool snapshots as first-class serving
        targets).  Runs on the inference service's thread at dispatch
        time: the live epoch answers the in-memory model; other epochs
        load their digest-verified checkpoint once and LRU-cache
        (``serving.snapshot_cache``), adopting the live model's
        compiled forward — params are jit arguments, so a routed
        snapshot costs a file read, never a recompile.  None (a typed
        error at the frontend) when the epoch was never committed or
        its file is pruned/corrupt."""
        if epoch == self.model_epoch:
            return self.model
        cache = self._serving_snapshots
        model = cache.get(epoch)
        if model is not None:
            cache.move_to_end(epoch)
            return model
        try:
            params = read_verified(model_path(epoch))["params"]
        except (OSError, CorruptCheckpointError, pickle.UnpicklingError,
                EOFError, KeyError):
            return None  # pruned / never committed / corrupt
        model = TPUModel(self.model.module, params)
        try:
            if hasattr(self.model, "_jitted"):
                model._jitted = self.model._jitted
        except Exception:
            pass
        cache[epoch] = model
        while len(cache) > int(self._serving_cfg.snapshot_cache):
            cache.popitem(last=False)
        return model

    def _serving_tick(self):
        """Once per server-loop pass: supervise the serving frontend —
        a dead acceptor respawns behind backoff and the fleet's
        windowed circuit breaker (a trip disables network serving for
        the rest of the run; training is never held hostage by the
        serving plane)."""
        fe = self.serve_frontend
        if (fe is None or fe.alive or self._serve_disabled
                or self.shutdown_flag):
            return
        now = time.monotonic()
        if self._serve_respawn_at == 0.0:
            if self._serve_window.record(now):
                self._serve_disabled = True
                print("ERROR: the serving frontend keeps dying "
                      "(circuit breaker tripped); network serving "
                      "disabled for this run — training continues")
                fe.close()
                return
            delay = float(self.args.get("respawn_backoff", 0.5) or 0.5)
            self._serve_respawn_at = now + delay
            print(f"WARNING: serving frontend died; respawning in "
                  f"{delay:.1f}s (clients see refused connections "
                  f"meanwhile)")
        elif now >= self._serve_respawn_at:
            self._serve_respawn_at = 0.0
            try:
                fe.respawn()
            except Exception as exc:
                # e.g. a fixed port still held elsewhere: the failure
                # must cost the serving plane (another ladder round,
                # eventually the breaker), never the server loop that
                # keeps training alive
                print(f"WARNING: serving frontend respawn failed "
                      f"({exc!r}); retrying through the backoff ladder")
                return
            self._serve_respawns += 1
            print("serving frontend respawned "
                  f"(incarnation {fe.generation})")
            if self.serve_announcer is not None:
                # the respawned frontend must re-enter the pool: the
                # announcer's fresh register bumps this replica's
                # registry generation — how the respawn is observed
                # pool-wide
                self.serve_announcer.respawn()

    def _router_tick(self):
        """Once per server-loop pass: supervise the pool router the
        way ``_serving_tick`` supervises the frontend — backoff
        respawn behind the windowed circuit breaker; a trip disables
        pool routing for the run, never training."""
        rt = self.router_frontend
        if (rt is None or rt.alive or self._router_disabled
                or self.shutdown_flag):
            return
        now = time.monotonic()
        if self._router_respawn_at == 0.0:
            if self._router_window.record(now):
                self._router_disabled = True
                print("ERROR: the pool router keeps dying (circuit "
                      "breaker tripped); pool routing disabled for "
                      "this run — training continues")
                rt.close()
                return
            delay = float(self.args.get("respawn_backoff", 0.5) or 0.5)
            self._router_respawn_at = now + delay
            print(f"WARNING: pool router died; respawning in "
                  f"{delay:.1f}s (pool clients see refused "
                  f"connections meanwhile)")
        elif now >= self._router_respawn_at:
            self._router_respawn_at = 0.0
            try:
                rt.respawn()
            except Exception as exc:
                print(f"WARNING: pool router respawn failed "
                      f"({exc!r}); retrying through the backoff "
                      f"ladder")
                return
            self._router_respawns += 1
            print(f"pool router respawned "
                  f"(incarnation {rt.generation})")
            if (self.serve_announcer is not None
                    and not self._serving_cfg.router_address):
                # the local announcer dials the router's port; with
                # port 0 a respawn rebinds fresh, so point it at the
                # new incarnation before its next retry
                self.serve_announcer.port = rt.port

    # -- server loop -------------------------------------------------
    def _on_beat(self, beats):
        # liveness bookkeeping happened in the server loop (the
        # registry needs the conn identity); the beat just needs an ack
        return [None for _ in beats]

    def _on_args(self, requests):
        if self.shutdown_flag:
            return [None for _ in requests]
        return [self._assign_job() for _ in requests]

    def _on_episode(self, episodes):
        self.feed_episodes(episodes)
        return [None for _ in episodes]

    def _on_result(self, results):
        self.feed_results(results)
        return [None for _ in results]

    def _on_model(self, model_ids):
        return [self._serve_model(mid) for mid in model_ids]

    def server(self):
        print("started server")
        handlers = {
            "args": self._on_args,
            "episode": self._on_episode,
            "result": self._on_result,
            "model": self._on_model,
            "beat": self._on_beat,
            "shm": self._on_shm,
        }
        next_epoch_at = (self.args["minimum_episodes"]
                         + self.args["update_episodes"])

        while self.worker.connection_count() > 0 or not self.shutdown_flag:
            if self.stall_watchdog is not None:
                self.stall_watchdog.beat("server")
            try:
                conn, (verb, payload) = self.worker.recv(timeout=0.3)
            except queue.Empty:
                conn = None  # epoch checks below still run on idle
            self._sweep_fleet()
            # shm trajectory intake + inference-service supervision
            # run every pass, so pipelined episodes tick the same
            # epoch cadence as control-plane arrivals below
            self._pipeline_tick()
            self._serving_tick()
            self._router_tick()

            if conn is not None:
                self.fleet.observe(conn, verb, payload)
                # gathers batch requests into lists; single requests
                # get a single reply back
                batched = isinstance(payload, list)
                handler = handlers.get(verb)
                if handler is None:
                    # unknown verb (version skew / stray client):
                    # reply empty so the peer is not wedged, and COUNT
                    # it — the runtime counterpart of commlint's
                    # unhandled-verb, surfaced as `unknown_verbs` in
                    # drop_stats()/the fleet metrics instead of being
                    # an invisible shrug
                    self.worker.note_unknown_verb(verb)
                    self.worker.send(conn, [] if batched else None)
                    continue
                # the request's trace context (adopted by the
                # communicator's recv codec) is current here, so this
                # span joins the sending worker's trace — the learner
                # side of the cross-process timeline
                with telemetry.trace_span("rpc." + str(verb)):
                    replies = handler(payload if batched else [payload])
                self.worker.send(
                    conn, replies if batched else replies[0])

            if self.multihost and not self.primary:
                # replicas don't decide epochs: they follow the trainer,
                # which follows process 0 through the control collective
                if (self.trainer.epoch > self.model_epoch
                        and not self.shutdown_flag):
                    self.update()
                if self.trainer.shutdown_flag:
                    self.shutdown_flag = True
                    self.worker.begin_drain()
            elif (self.trainer.anakin is not None
                    and not self.shutdown_flag
                    and self.trainer.failure is not None):
                # a dead fused loop can never advance the step clock,
                # and nothing else ticks anakin epochs — an idle spin
                # here would serve a frozen model forever, so exit
                # LOUDLY instead (the IMPALA path instead degrades to
                # serving the last snapshot, because intake keeps its
                # epoch cadence alive)
                print("ERROR: anakin trainer thread failed "
                      f"({self.trainer.failure!r}); shutting down — "
                      "nothing advances epochs without the fused loop")
                self.shutdown_flag = True
                self.worker.begin_drain()
            elif (self.trainer.anakin is not None
                    and not self.shutdown_flag
                    and self.trainer.steps >= self._anakin_epoch_at):
                # anakin: the fused loop makes its own data, so the
                # epoch clock is the trainer's step count, not intake
                self._anakin_epoch_at += self.args["updates_per_epoch"]
                self.update()
                if 0 <= self.args["epochs"] <= self.model_epoch:
                    self.shutdown_flag = True
                    self.worker.begin_drain()
            # episodes drained from worker pools after shutdown still
            # land in the buffer but must not start extra epochs
            elif (self.episodes_received >= next_epoch_at
                    and not self.shutdown_flag):
                next_epoch_at += self.args["update_episodes"]
                self.update()
                if 0 <= self.args["epochs"] <= self.model_epoch:
                    self.shutdown_flag = True
                    # workers drain from here: gather exits become
                    # expected completions, not respawnable crashes
                    self.worker.begin_drain()
        print("finished server")

    def _league_opponent(self):
        """Sample a past checkpoint epoch for a league seat, or None.

        Candidates are the epochs from the last ``past_epochs`` whose
        snapshot file actually survives retention pruning — sampling a
        pruned epoch would silently serve the latest model under a
        stale label (``_serve_model``'s fallback)."""
        cfg = self.args.get("generation_opponent") or {}
        k = int(cfg.get("past_epochs", 0) or 0)
        if k <= 0 or self.model_epoch < 2:
            return None
        if random.random() >= float(cfg.get("prob", 0.25)):
            return None
        lo = max(1, self.model_epoch - k)
        cands = [e for e in range(lo, self.model_epoch)
                 if os.path.exists(model_path(e))]
        return random.choice(cands) if cands else None

    def _assign_job(self):
        """Split worker jobs between generation and evaluation so that
        evaluation keeps pace at ``eval_rate`` of the episode stream.
        With ``generation_opponent`` configured, a fraction of
        generation jobs seat a retained past self as one opponent
        (league-lite); those jobs carry mixed snapshots, so the actor
        pool routes them down its sequential path."""
        players = self.env.players()
        league_seat = past = None
        # anakin mode: generation runs on-device inside the fused
        # step, so the worker fleet is evaluation-only — every job is
        # an eval match and the win-rate stream keeps its cadence
        wants_eval = (
            getattr(self.trainer, "anakin", None) is not None
            or self.jobs_evaluated < self.eval_rate * self.jobs_generated)
        if wants_eval:
            seat = self.jobs_evaluated % len(players)
            trained = [players[seat]]
            self.jobs_evaluated += 1
            role = "e"
        else:
            trained = list(players)
            past = self._league_opponent()
            if past is not None:
                league_seat = random.choice(players)
                trained = [p for p in players if p != league_seat]
            self.jobs_generated += 1
            role = "g"
        model_id = {
            p: self.model_epoch if p in trained else -1
            for p in players
        }
        if league_seat is not None:
            model_id[league_seat] = past
        return {"role": role, "player": trained, "model_id": model_id}

    def _serve_model(self, model_id):
        model = self.model
        if model_id != self.model_epoch and model_id > 0:
            try:
                with open(model_path(model_id), "rb") as f:
                    state = pickle.load(f)
                model = TPUModel(self.model.module, state["params"])
            except (OSError, pickle.UnpicklingError, EOFError):
                pass  # missing/corrupt snapshot: serve the latest model
        return pickle.dumps(model)

    def run(self):
        trainer_thread = threading.Thread(
            target=self.trainer.run, daemon=True)
        trainer_thread.start()
        self.worker.run()
        try:
            self.server()
        finally:
            # stop device work before interpreter teardown: a daemon
            # thread mid-update during exit crashes the XLA runtime.
            # Feeds stop only after the thread exits — a committed
            # multihost step still needs its batch (see stop_feeds)
            self.trainer.request_shutdown()
            trainer_thread.join(timeout=30)
            self.trainer.stop_feeds()
            self.worker.shutdown()
            if self.stall_watchdog is not None:
                # after shutdown the loops stop beating by design; a
                # late sample must not report teardown as a stall
                self.stall_watchdog.stop()
            if self.status is not None:
                self.status.close()
            if self.serve_announcer is not None:
                # graceful goodbye FIRST: the router drains this
                # replica (in-flight forwards finish, nothing new
                # routes here) before its listener goes away
                self.serve_announcer.close()
            if self.router_frontend is not None:
                self.router_frontend.close()
            if self.serve_frontend is not None:
                # the frontend rides the service: close it first so no
                # handler thread submits into a closing service
                self.serve_frontend.close()
            if self.infer_service is not None:
                # workers are gone (shutdown drained them): unmap and
                # unlink every ring this learner created
                self.infer_service.close()
            if self.wal is not None:
                self.wal.close()  # final fsync of the append tail
            telemetry.flush()  # ship the span-log tail before exit
        if self.trainer.failure is not None:
            # the in-run degradation (serve the last model, keep the
            # epoch cadence) kept the fleet alive; the RUN still
            # failed, and its exit code must say so — a supervisor or
            # a shell sees non-zero, never a green run with steps: 0
            raise RuntimeError(
                "training ended with a dead trainer thread"
            ) from self.trainer.failure


def _maybe_init_distributed(args):
    """Multi-host bring-up must precede any jax device use, so it runs
    at the mode entry point, before envs or models touch the backend."""
    dist_cfg = (args.get("train_args") or {}).get("distributed")
    if dist_cfg:
        from .parallel.multihost import init_distributed

        init_distributed(dist_cfg)
        print(f"distributed: process {jax.process_index()} of "
              f"{jax.process_count()}, {jax.local_device_count()} local "
              f"/ {jax.device_count()} global devices")


def _train_local(args):
    """One learner incarnation (the supervised-child entry point —
    module-level so the spawn context can pickle it).  Returns the
    finished learner: in-process callers read its counters."""
    _maybe_init_distributed(args)
    prepare_env(args["env_args"])
    learner = Learner(args=args)
    learner.run()
    return learner


def _train_remote(args):
    _maybe_init_distributed(args)
    learner = Learner(args=args, remote=True)
    learner.run()


def _maybe_supervised(args, target):
    """``supervise_learner: true`` runs the learner as a guarded child
    process: a crash or preemption relaunches it with ``restart_epoch:
    auto`` behind the fleet's backoff/circuit-breaker policy
    (resilience.guardian.LearnerGuard), so recovery needs no operator.
    Returns True when the guard ran (and has already finished)."""
    if not (args.get("train_args") or {}).get("supervise_learner"):
        return False
    from .resilience.guardian import LearnerGuard

    code = LearnerGuard.from_args(target, args).run()
    if code:
        raise SystemExit(code)
    return True


def train_main(args):
    """``main.py --train``.  Returns the finished learner, or None
    when a supervised child ran it."""
    if not _maybe_supervised(args, _train_local):
        return _train_local(args)


def train_server_main(args):
    if not _maybe_supervised(args, _train_remote):
        _train_remote(args)
