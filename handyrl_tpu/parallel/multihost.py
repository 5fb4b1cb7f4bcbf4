"""Multi-host (multi-process) learner support.

The north-star workload runs on a TPU pod — e.g. Hungry Geese on a
v4-32, which is FOUR hosts each owning 8 chips.  A single-process mesh
cannot address that: JAX's multi-controller model runs one Python
process per host, every process executing the same jitted program over
one global mesh, with XLA routing collectives over ICI/DCN.

This module is the thin seam between that model and the learner:

  * ``init_distributed``   — process bring-up (``jax.distributed``),
    called once before any device use; on Cloud TPU pods it
    auto-detects topology, elsewhere (tests, CPU rehearsal) it takes
    explicit ``coordinator_address`` / ``num_processes`` /
    ``process_id``.
  * ``global_batch_from_local`` — every process feeds ITS OWN batch
    shard (from its own actor fleet + replay, the distributed-IMPALA
    layout); ``jax.make_array_from_process_local_data`` assembles the
    global arrays without any cross-host data movement.
  * ``sync_epoch_code``    — the one-word control collective that keeps
    epoch boundaries aligned: process 0 (which owns reporting and
    checkpointing) decides, everyone obeys.

Capability replaced: the reference tops out at one machine's GPUs via
``nn.DataParallel`` (/root/reference/handyrl/train.py:340-341); its
docs scale ACTORS across machines but never the learner
(/root/reference/docs/large_scale_training.md).

Operational requirements (standard for multi-controller JAX):
  * all processes run the same config (global ``batch_size`` divisible
    by ``num_processes``; same mesh, same seeds);
  * for ``restart_epoch`` resume, the checkpoint dir must be visible to
    every process (shared filesystem) — process 0 writes, and the
    restored state is broadcast so replicas can never cold-start into
    divergence;
  * a process that dies mid-epoch stalls the collective; the
    ``jax.distributed`` runtime's heartbeat then fails the job (crash =
    job restart, the same contract every SPMD framework has).
"""

from typing import Any, Dict, Optional

import jax
import numpy as np

# epoch-control words for sync_epoch_code
STEP = 0        # keep training: every process must run one more step
EPOCH_END = 1   # finish the epoch: snapshot + report, then loop
STOP = 2        # end training entirely


def init_distributed(cfg: Optional[Dict[str, Any]]) -> bool:
    """Bring up ``jax.distributed`` from the ``distributed:`` config
    section.  Empty/None = single-process (no-op, returns False).

    Keys (all optional on Cloud TPU pods, where topology auto-detects):
      coordinator_address — "host:port" of process 0
      num_processes, process_id — explicit topology
      local_device_ids    — restrict this process's local devices

    Must run before the first jax computation in the process.
    """
    if not cfg:
        return False
    allowed = {"coordinator_address", "num_processes", "process_id",
               "local_device_ids", "auto"}
    unknown = set(cfg) - allowed
    if unknown:
        raise ValueError(f"unknown distributed config keys: "
                         f"{sorted(unknown)}")
    # CPU cross-process collectives (tests / pod rehearsal) need an
    # explicit transport; gloo ships with jaxlib.  Set unconditionally
    # BEFORE any backend probe — even ``jax.default_backend()`` would
    # initialize the client, and distributed init must come first.
    # The knob only affects the cpu platform, so it is harmless on TPU.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    for key in ("coordinator_address", "num_processes", "process_id",
                "local_device_ids"):
        if cfg.get(key) is not None and cfg.get(key) != "":
            kwargs[key] = cfg[key]
    jax.distributed.initialize(**kwargs)
    return True


def process_count() -> int:
    return jax.process_count()


def is_primary() -> bool:
    """Process 0 owns checkpoints, metrics, and epoch decisions."""
    return jax.process_index() == 0


def local_batch_size(global_batch_size: int) -> int:
    """Rows THIS process's batchers must produce per step."""
    n = jax.process_count()
    if global_batch_size % n != 0:
        raise ValueError(
            f"batch_size {global_batch_size} must be divisible by the "
            f"process count {n} (every process feeds an equal shard)")
    return global_batch_size // n


def global_batch_from_local(local_batch, sharding):
    """Assemble global device arrays from this process's batch shard.

    ``local_batch`` is a pytree of host numpy arrays holding this
    process's rows (``local_batch_size`` of the global batch dim).
    Purely local work — device_puts to addressable devices plus
    metadata; no collectives, so prefetch threads may run it at their
    own pace on every host.

    Wire-format note: bf16 leaves ship as numpy bfloat16 directly.  The
    single-host path bitcasts uint16 on device instead (learner
    ``_stage_batch``) because that's measurably faster through PJRT,
    but the bitcast is a jitted computation — a collective program
    launch on a global array, which unsynchronized prefetch threads
    must never issue.  Decode-before-assembly keeps staging local.
    """
    return jax.tree.map(
        lambda a: jax.make_array_from_process_local_data(sharding, a),
        local_batch,
    )


def replay_group_size(mesh) -> int:
    """Devices per batch-replication group: batch rows shard over
    ``dp`` and replicate across ``sp``/``tp``, and the global mesh is
    ``jax.devices()`` (process-major) reshaped row-major to
    (dp, sp, tp) — so each dp coordinate owns ``sp*tp`` consecutive
    devices."""
    return mesh.shape["sp"] * mesh.shape["tp"]


def local_replay_mesh(mesh):
    """Per-process ``("dp", "rep")`` mesh for a local HBM replay ring
    under a global (dp, sp, tp) mesh.

    Local devices are taken in GLOBAL enumeration order and grouped in
    runs of ``rep = sp*tp``, so each local dp group coincides exactly
    with a global replication group: a local gather that shards rows
    over ``dp`` and replicates across ``rep`` lays every row out on
    precisely the devices the GLOBAL batch sharding wants it on.
    Caller must have checked ``local_device_count() % rep == 0``
    (dp groups process-local)."""
    from jax.sharding import Mesh

    rep = replay_group_size(mesh)
    local = [d for d in jax.devices()
             if d.process_index == jax.process_index()]
    return Mesh(np.asarray(local).reshape(len(local) // rep, rep),
                ("dp", "rep"))


def global_from_local_shards(local_batch, sharding):
    """Assemble global batch arrays from per-device local shards that
    are ALREADY laid out to match ``sharding`` (the local replay
    gather over ``local_replay_mesh``).  Pure metadata: no device or
    host data movement."""
    n_proc = jax.process_count()

    def leaf(arr):
        shards = [s.data for s in arr.addressable_shards]
        gshape = (arr.shape[0] * n_proc,) + arr.shape[1:]
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, shards)

    return jax.tree.map(leaf, local_batch)


def sync_epoch_code(code: int) -> int:
    """All-process agreement on the epoch-control word.

    Every process calls this once per training-loop iteration; the
    value from process 0 wins (STEP / EPOCH_END / STOP above).  Doubles
    as the step barrier that keeps every process's update-step count
    identical — which in turn keeps the host-side lr anneal identical,
    since it is driven by (global) metrics and the shared step count.
    """
    from jax.experimental import multihost_utils

    out = multihost_utils.broadcast_one_to_all(
        np.asarray(code, dtype=np.int32))
    return int(out)


def broadcast_train_state(params, opt_state, steps, data_cnt_ema):
    """One-time broadcast of process 0's full train state at startup.

    Replicas then provably start from identical state even when only
    process 0 could read a restart checkpoint, or when env-dependent
    init produced per-host differences.  Cheap insurance: runs once,
    off the hot path.
    """
    from jax.experimental import multihost_utils

    host = jax.tree.map(np.asarray, (params, opt_state))
    params, opt_state = multihost_utils.broadcast_one_to_all(host)
    # floats cross the device as float32 when x64 is off, so a raw
    # step count would silently round above 2^24; two 24-bit words
    # survive the trip exactly for any count below 2^48
    scalars = multihost_utils.broadcast_one_to_all(np.asarray(
        [steps // (1 << 24), steps % (1 << 24), data_cnt_ema],
        np.float64))
    steps = int(scalars[0]) * (1 << 24) + int(scalars[1])
    return params, opt_state, steps, float(scalars[2])
