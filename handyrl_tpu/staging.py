"""Device-resident episode staging: the replay buffer lives in HBM.

The reference (and this repo's fallback path) assembles every training
batch on the host: sample episodes, decompress, gather/pad numpy, ship
the result to the device (/root/reference/handyrl/train.py:271-319).
On a learner whose update step takes milliseconds that host work IS
the training loop, and the device idles through most of it.

``DeviceReplay`` inverts the layout, TPU-first:

  * each finished episode is decompressed and columnarized ONCE, then
    uploaded into a ring of fixed-shape device buffers (obs rides the
    compact wire dtype — bf16 or uint8 — so HBM cost is half/quarter
    of f32);
  * every training batch is built ON DEVICE by one jitted gather: the
    host contributes only three small int32 vectors per draw (episode
    slot, window start, seat), and XLA fuses the window fetch into a
    single gather from the flat ring;
  * masks, padding, value bootstrap, progress — all the ``make_batch``
    semantics — are recomputed inside the same jit from episode
    lengths, equal to the host path (tests/test_staging.py pins batch
    equality draw by draw).

Per-step feed cost collapses from "assemble + transfer ~20 MB on the
host" to "transfer ~3 KB of indices", and the per-episode upload is
amortized over every draw of that episode (recency-biased sampling
draws each episode many times per epoch).

Storage layout: per-step channels are TWO-dimensional
``(CAP * T_max, flat_features)`` arrays (slot-major time, trailing
dims flattened), so a window fetch is ONE gather with indices
``slot * T_max + t`` — never materializing a ``(B, T_max, ...)``
intermediate — and, critically, the persistent ring pads to the TPU's
(8, 128) tile with ~1% overhead.  Keeping logical trailing dims (e.g.
``(N, P, 6, 6, 7)``) instead would tile-pad the ring up to ~24x and
OOM the device (observed on Geister: a 2 GB ring became a 47 GB
allocation).  Wide buffers are stored with whole 128-lane rows
(``_stored_width``) and the per-step scalars and masks ride ONE
int32 channel, the masks packed (``_pack_steps``), so the gather reads
the ring in place and only the batch's rows of it.  The gather
reshapes windows back to logical shapes in-jit, where they are
transient activations XLA lays out freely; in ``seat`` mode it keeps
the drawn seat's columns of an observation row before that re-lay,
not after it.
Per-slot channels (outcome, lengths) are ``(CAP + 1, ...)``; the +1
and an extra ``_RUN_ROUND``-row stripe past the ring are SCRATCH that
batched-append padding scatters into and no gather ever reads.

Concurrency contract: appends and samples MUST run on one thread (the
trainer thread calls ``ingest`` between update steps).  Both jits
donate the buffers, so interleaving from two threads would race the
donation.  The learner's server thread only enqueues raw episodes into
``pending`` (thread-safe under the internal lock).
"""

import random
import threading
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from .batch import BF16, ILLEGAL, _build_columnar
from .telemetry import spans as _telemetry
from .telemetry.inflight import InFlight
from .utils.tree import tree_map


def make_replay_update_step(replay, model, loss_cfg, optimizer,
                            compute_dtype, batch_size, mesh=None,
                            params=None, fsdp=False, seed=0):
    """ONE jitted program per training step: index draw -> ring gather
    -> loss -> grad -> Adam.  Everything happens on device — the host
    contributes three SCALARS per call (ring fill, oldest slot, step
    counter), so a training step uploads nothing at all.  The draw
    folds the step counter into a fixed PRNG key and reproduces the
    triangular recency bias + uniform window/seat choice in-jit.

    With a mesh, params/optimizer keep their usual shardings while the
    ring rides replicated and the gathered batch is constrained onto
    ``dp`` — each device materializes only its own batch rows.

    ``state`` is the pair ``step_state`` makes, donated and threaded:
    the ring's three scalars, and the epoch's running sums of what the
    epoch boundary reads of ``metrics`` (``epoch_sums``).  The step
    returns its own ``metrics`` as before and adds them to the sums, so
    the boundary fetches a dozen scalars whatever the epoch's length,
    not one device scalar per metric per step.

    Under ``update_algorithm: impact`` the signature grows the target
    params (same treatment as ``params``): ``step(params, opt_state,
    buffers, state, target_params)`` returning the refreshed target as
    its last element — still ONE jitted program per training step.
    """
    from .ops.update import make_update_core

    core = make_update_core(model, loss_cfg, optimizer, compute_dtype)
    impact = loss_cfg.update_algorithm == "impact"
    base_key = jax.random.PRNGKey(seed)

    def _draw(buffers, ring):
        # ring = the first half of ``state``, device int32 [size,
        # oldest, step_idx]: keeping the draw scalars ON DEVICE and
        # threading the step counter through the jit means a
        # steady-state step uploads NOTHING
        size, oldest, step_idx = ring[0], ring[1], ring[2]
        # the scopes are HLO metadata only (the step's phases on a
        # device trace, telemetry/devtrace.py): they change no operation
        with jax.named_scope("replay.draw"):
            slots, tstarts, seats = replay._draw_on_device(
                buffers, size, oldest, step_idx, base_key, batch_size)
        with jax.named_scope("replay.gather"):
            batch = replay._gather_batch(buffers, slots, tstarts, seats)
            if replay._out is not None:
                batch = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, replay._out), batch)
        return batch

    def _advance(state, metrics):
        ring, sums = state
        # a dozen scalar adds, beside the gradient norm and the
        # nonfinite flag they read: no phase of their own on a trace
        with jax.named_scope("optimizer"):
            sums = dict(sums, steps=sums["steps"] + 1)
            for key in LOSS_SUMS + COUNT_SUMS:
                if key in metrics:
                    sums[key] = sums[key] + metrics[key].astype(
                        sums[key].dtype)
        return ring + jnp.asarray([0, 0, 1], jnp.int32), sums

    if impact:
        def step(params, opt_state, buffers, state, target_params):
            batch = _draw(buffers, state[0])
            p, o, metrics, t = core(params, opt_state, batch,
                                    target_params)
            return p, o, metrics, _advance(state, metrics), t
    else:
        def step(params, opt_state, buffers, state):
            batch = _draw(buffers, state[0])
            p, o, metrics = core(params, opt_state, batch)
            return p, o, metrics, _advance(state, metrics)

    if mesh is None:
        if impact:
            return jax.jit(step, donate_argnums=(0, 1, 3, 4))
        return jax.jit(step, donate_argnums=(0, 1, 3))

    from .parallel.mesh import param_sharding, replicated
    from .parallel.update import opt_state_sharding

    p_shard = param_sharding(mesh, params, fsdp=fsdp)
    rep = replicated(mesh)
    o_shard = opt_state_sharding(optimizer, params, p_shard, rep)
    if impact:
        return jax.jit(
            step,
            in_shardings=(p_shard, o_shard, rep, rep, p_shard),
            out_shardings=(p_shard, o_shard, rep, rep, p_shard),
            donate_argnums=(0, 1, 3, 4),
        )
    return jax.jit(
        step,
        in_shardings=(p_shard, o_shard, rep, rep),
        out_shardings=(p_shard, o_shard, rep, rep),
        donate_argnums=(0, 1, 3),
    )


# What the epoch boundary reads of a fused step's ``metrics``
# (learner.Trainer._finish_epoch), every one of them as a sum over the
# epoch's steps.  The losses sum in float32; the counts are whole
# numbers (``dcnt`` sums a 0/1 mask, ``nonfinite`` is a 0/1 flag) and
# sum in int32: ``dcnt`` feeds the learning rate, and float32 is exact
# only to 2**24, 8192 steps of 2048 rows.
LOSS_SUMS = ("p", "v", "r", "ent", "total", "clip_frac")
COUNT_SUMS = ("dcnt", "nonfinite")


def epoch_sums(replay):
    """The running sums of ``state`` at the start of an epoch: zeros
    for every key a step may add to (one its ``metrics`` lack stays
    zero), and ``steps``, the steps summed.  The run's first and every
    later epoch's come from HERE, as the ring's scalars come from
    ``device_state``: host zeros put on the device (replicated under a
    mesh), which lowers no program, and looks to the step's ``jit``
    at every epoch's first step as it did at the run's."""
    sums = {key: np.zeros((), np.float32) for key in LOSS_SUMS}
    sums.update({key: np.zeros((), np.int32)
                 for key in COUNT_SUMS + ("steps",)})
    # no sharding: uncommitted on the default device, as ``jnp.asarray``
    # leaves the ring's scalars
    return jax.device_put(sums, replay._rep)


def step_state(replay, step_idx):
    """The fused step's fourth argument at the start of an epoch:
    ``(replay.device_state(step_idx), epoch_sums(replay))``.  When the
    ring moves mid-epoch only the first half is made anew."""
    return replay.device_state(step_idx), epoch_sums(replay)


_GROW_ROUND = 32   # T_max granularity; growth doubles => few recompiles
# episode uploads pad to _GROW_ROUND-row buckets (not full t_max
# stripes: ~6x less wire traffic at real episode-length spreads) and
# each append batch pads its TOTAL rows to _RUN_ROUND so the scatter
# jit sees a handful of shapes; padding rows land in a scratch stripe
# past the ring that no gather ever reads
_RUN_ROUND = 256
_MAX_RUN = 8       # per-slot scatter width (ingest batch cap)
_MAX_RUN_ROWS = 16384   # rows one scatter carries at most
_PER_SLOT = ("outcome", "ep_len", "ep_total")


def _run_geometry(t_win):
    """``(_RUN_ROUND, _MAX_RUN)`` for a ring whose training window is
    ``t_win`` steps.  An append program exists per ``_RUN_ROUND`` rows
    up to ``_MAX_RUN`` whole slots: at a window of a dozen steps and
    episodes of a few hundred that is a handful (256 rows, 8 episodes),
    at a window that is a 4,096-token sequence it would be 128.  So the
    bucket is never narrower than the window and a run never carries
    more than ``_MAX_RUN_ROWS`` rows: 4 programs of 4,096 to 16,384
    rows there, and what it was wherever the window fits 256 rows."""
    bucket = max(256, _round_up(t_win, 256))
    return bucket, max(1, min(8, _MAX_RUN_ROWS // bucket))


def fit_runs_to_window(t_win):
    """Set the module's ``_RUN_ROUND`` and ``_MAX_RUN`` to
    ``_run_geometry(t_win)`` and return them.  A ring keeps its own
    pair (fixed for the run); the module's names say what the window
    declared last uses, which is where the benchmark's priming reads
    them -- a ring declares its window as it is built, and an
    environment whose episode IS the window (``envs/token_task.py``) as
    it is made, for a tool that primes and builds no ring."""
    global _RUN_ROUND, _MAX_RUN
    _RUN_ROUND, _MAX_RUN = _run_geometry(t_win)
    return _RUN_ROUND, _MAX_RUN


def _decompress_episode(ep):
    """Full-episode columnar arrays from the wire format (bz2 or raw
    pickle moment blocks, magic-sniffed per block — see
    batch.load_block).  Runs once per episode at ingest."""
    from .batch import load_block

    moments = [m for blob in ep["moment"] for m in load_block(blob)]
    col = _build_columnar(moments)
    col["outcome"] = np.asarray(
        [ep["outcome"][p] for p in col["players"]],
        np.float32).reshape(-1, 1)
    col["steps"] = ep["steps"]
    return col


def _round_up(n, k=_GROW_ROUND):
    return ((n + k - 1) // k) * k


def _stored_width(width):
    """Columns a ``(rows, width)`` ring buffer is STORED with: a wide
    buffer whose width is not a multiple of the 128-lane tile gets a
    rows-minor device layout, and the row gather then re-lays the
    WHOLE buffer first — a ring-sized copy (and a ring-sized
    temporary) inside every training step, seen in the TPU compiler's
    ``memory_analysis`` (tests/test_tpu_compile.py).  Padded to whole
    lanes the gather reads the ring in place; the padding columns
    (5236 -> 5248 for Hungry Geese) are never read back."""
    return width if width <= 128 else _round_up(width, 128)


def _mask_words(P, A):
    """32-bit words one ring row's masks pack into: ``omask`` P bits,
    ``tmask`` P bits, ``amask`` P*A bits, in that order, bit ``k`` of
    the row at bit ``k % 32`` of word ``k // 32``."""
    return -(-P * (A + 2) // 32)


def _steps_width(P, A, riders=0):
    """Columns of the ring's ``steps`` channel (``_pack_steps``);
    ``riders``: observation leaves that ride it."""
    return (5 + riders) * P + 1 + _mask_words(P, A)


def _rides_steps(leaf):
    """An observation leaf of ONE integer a seat (a token: ``(T, P)``)
    rides the ``steps`` channel, ``P`` columns after the turn index: a
    ring channel of its own one column wide would be re-laid ring-wide
    by the row gather (``_pack_steps``)."""
    return leaf.ndim == 2 and np.issubdtype(leaf.dtype, np.integer)


def _pack_steps(col):
    """A columnar episode's per-step scalars -> the ``(T,
    _steps_width(P, A))`` int32 rows of the ring's ``steps`` channel:
    ``prob``, ``act``, ``value``, ``reward``, ``return`` (P columns
    each, the float ones as their bits), the turn index, the
    observation's integer-scalar leaves (``_rides_steps``; none for a
    board game), then the three masks' bits (host side, once per
    episode; ``_gather_batch`` takes the row apart again, bit for
    bit).  An all-legal episode has an action mask of width 0 and so no
    mask bits but the two seat masks'.

    ONE channel of 32-bit elements, and not one per quantity, because
    of what the TPU's row gather does with a narrow channel (asked of
    the compiler and read off the chip's trace; tests/
    test_tpu_compile.py holds it).  One-byte elements (the masks as
    bools) it re-lays row-major first: the WHOLE ring, inside every
    training step.  A channel one element wide it re-lays to one
    dimension, ring-wide too.  And each channel of a few 32-bit
    columns it copies whole into fast memory before the gather, in
    quarters, every step: the step's time grew with the ring's rows.
    One wider channel is read in place, the batch's rows alone (or,
    where it fits the fast memory whole, rides the compiler's one
    prefetch across calls)."""
    T = len(col["turn_idx"])

    def i32(key):
        return np.reshape(col[key], (T, -1)).astype(np.int32)

    def f32_bits(key):
        return np.reshape(col[key], (T, -1)).astype(np.float32).view("<i4")

    bits = np.concatenate(
        [np.reshape(col[key], (T, -1)) != 0
         for key in ("omask", "tmask", "amask")], axis=1)
    bits = np.pad(bits, [(0, 0), (0, -bits.shape[1] % 32)])
    riders = [leaf.reshape(T, -1).astype(np.int32)
              for leaf in jax.tree.leaves(col["obs"]) if _rides_steps(leaf)]
    return np.concatenate(
        [f32_bits("prob"), i32("act"), f32_bits("value"),
         f32_bits("reward"), f32_bits("return"), i32("turn_idx")]
        + riders
        + [np.packbits(bits, axis=1, bitorder="little").view("<i4")],
        axis=1)


class DeviceReplay:
    """Ring buffer of episodes in device memory + jitted batch gather.

    ``mode`` mirrors ``make_batch``'s player selection
    (batch.py _episode_tensors):
      turn — turn-based training: acting channels gather the turn
             player (P_in=1), value channels keep all players
      seat — simultaneous games: ONE random seat per draw, all channels
      all  — observation mode: all players, all channels
    """

    def __init__(self, cfg, capacity, max_bytes, max_steps_hint=0,
                 mesh=None):
        self.cfg = cfg
        # single-process multi-chip: the ring is REPLICATED over the
        # mesh (appends are cheap; HBM budget applies per device) and
        # the sample jit emits dp-sharded batches — each device gathers
        # only its own batch rows, so sampling scales with the mesh
        self._rep = None
        self._out = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._rep = NamedSharding(mesh, P())
            self._out = NamedSharding(mesh, P("dp"))
        self.capacity = int(capacity)   # may shrink to fit max_bytes
        self.max_bytes = int(max_bytes)
        self.forward_steps = cfg["forward_steps"]
        self.burn_in = cfg.get("burn_in_steps", 0) or 0
        self.t_win = self.burn_in + self.forward_steps
        # the append programs' geometry follows the window
        self.run_round, self.max_run = fit_runs_to_window(self.t_win)
        if cfg["turn_based_training"]:
            self.mode = "all" if cfg.get("observation") else "turn"
        else:
            self.mode = "seat"
        obs_wire = cfg.get("transfer_dtype") or ""
        self.obs_store = {"bfloat16": BF16, "uint8": np.uint8}.get(
            obs_wire, np.float32)
        self.compute_dtype = cfg.get("compute_dtype") or "bfloat16"

        self.t_max = _round_up(max(max_steps_hint, self.t_win))
        self.buffers = None        # device pytree
        self.num_players = None
        self.num_actions = None
        self._append_fn = None
        self._sample_fn = None

        # host-side mirrors (sampling math reads these, never devices)
        self._rng = None             # lazily seeded from `random`
        self.ep_len = None
        self.write_ptr = 0         # next slot (FIFO ring)
        self.size = 0              # filled slots
        self.episodes_seen = 0
        self.growths = 0           # T_max growth count: each one is a
        #                            LEGITIMATE recompile of the fused
        #                            step (the trainer widens its
        #                            RetraceGuard budget by this)

        # server thread -> trainer thread handoff
        self.pending = deque()
        self._offered_at = deque()   # telemetry-clock stamp per pending
        self.pending_cap = 512
        self.dropped = 0
        self._lock = threading.Lock()
        self._state_dirty = True   # ring changed since last device_state
        # the steps in flight, polled between the parts of an ingest:
        # the ledger of the trainer whose thread calls ``ingest`` (it
        # hands its own over); until then one that never holds a step
        self.inflight = InFlight()

    def device_state(self, step_idx):
        """Device int32 ``[size, oldest, step_idx]``: the ring's half
        of the fused update step's ``state`` (make_replay_update_step,
        ``step_state``).  Uploaded once here and then THREADED through
        the jit (which returns it with the step counter advanced), so
        steady-state steps upload nothing; call again only when
        ``state_dirty`` says an append/growth moved the ring."""
        self._state_dirty = False
        arr = jnp.asarray(
            np.asarray([self.size, self.oldest, step_idx], np.int32))
        if self._rep is not None:
            arr = jax.device_put(arr, self._rep)
        return arr

    @property
    def state_dirty(self):
        return self._state_dirty

    # -- ingest -------------------------------------------------------

    def offer(self, episodes):
        """Learner-server-thread side: queue raw episodes for the
        trainer thread.  Bounded: a stalled trainer sheds the OLDEST
        pending episodes rather than growing without limit."""
        episodes = [e for e in episodes if e is not None]
        with self._lock:
            self.pending.extend(episodes)
            # the stamp behind ``ingest.append``'s ``wait_ms``: taken
            # here, kept in step with ``pending``, shed with it
            self._offered_at.extend([_telemetry.now()] * len(episodes))
            while len(self.pending) > self.pending_cap:
                self.pending.popleft()
                self._offered_at.popleft()
                self.dropped += 1

    def ingest(self, max_episodes=64, batch=8):
        """Trainer-thread only: move pending episodes into the device
        ring.  Bounded per call so one call can't stall an update.

        Up to ``batch`` episodes upload as ONE device scatter —
        per-dispatch latency, not bandwidth, dominates small uploads —
        and each episode ships only its bucket-rounded length, not a
        full t_max stripe."""
        if not self.pending:
            return      # an empty call records no span
        with _telemetry.trace_span("trainer.ingest") as span:
            episodes = self.episodes_seen
            self._ingest(max_episodes, min(batch, self.max_run))
            span.attrs["episodes"] = self.episodes_seen - episodes

    def _ingest(self, max_episodes, batch):
        if self.buffers is None:
            # size T_max from everything already waiting (the warmup
            # backlog usually contains a near-maximal episode, saving
            # most growth recompiles later)
            with self._lock:
                if self.pending:
                    self.t_max = max(
                        self.t_max,
                        _round_up(max(e["steps"]
                                      for e in self.pending if e)))
        done = 0
        while done < max_episodes:
            raw, stamps = [], []
            with self._lock:
                while self.pending and len(raw) < batch:
                    raw.append(self.pending.popleft())
                    stamps.append(self._offered_at.popleft())
            if not raw:
                return
            with _telemetry.trace_span("ingest.decompress"):
                cols = []
                for ep in raw:
                    cols.append(_decompress_episode(ep))
                    self.inflight.poll("ingest.decompress")
            for col, stamp in zip(cols, stamps):
                col["offered_at"] = stamp
            done += len(cols)
            # batched is the ONLY path: size/allocate/grow decisions
            # are taken once over the whole run, then the run lands as
            # one device scatter (the legacy per-episode `_append`
            # dispatch measured ~12x slower and is gone)
            need = max(len(c["turn_idx"]) for c in cols)
            if self.buffers is None:
                if need > self.t_max:
                    self.t_max = _round_up(need)
                self._init_buffers(cols[0])
            elif need > self.t_max:
                self._grow(_round_up(max(need, self.t_max * 2)))
            while cols:
                # never more episodes than ring slots in one scatter:
                # repeated slot indices would mix trajectories
                # (undefined duplicate-index winner)
                run = cols[:min(self.capacity, self.max_run)]
                self._append_run(run)
                del cols[:len(run)]

    def warm_start(self, episodes):
        """Restore a replayed backlog (durability WAL) straight into
        the ring on the CALLER's thread, bypassing the bounded
        ``pending`` handoff (whose shed-oldest cap exists for a live
        stalled trainer, not for a finite resume replay).  MUST run
        before the trainer thread starts — same single-thread contract
        as ``ingest``.  Returns the number of episodes staged."""
        count = 0
        chunk = []
        for episode in episodes:
            if episode is None:
                continue
            chunk.append(episode)
            if len(chunk) >= 64:
                self.offer(chunk)
                self.ingest(max_episodes=len(chunk))
                count += len(chunk)
                chunk = []
        if chunk:
            self.offer(chunk)
            self.ingest(max_episodes=len(chunk))
            count += len(chunk)
        return count

    # -- buffer management -------------------------------------------

    def _per_slot_bytes(self, col):
        """HBM bytes one ring slot will occupy (capacity sizing).

        Counts what the TPU lays out, not logical bytes.  A persistent
        2-D ``(rows, width)`` buffer rides its ROWS on the 128-lane
        axis, and its width pads to the sublane tile — 1, 2, 4, or a
        multiple of 8 elements — so the channel of per-step scalars
        and masks (``_pack_steps``) costs its few dozen words a row,
        not a 128-wide stripe each.  Read off a v5e (``Array.format``
        + ``memory_stats`` on the chip) and held to the TPU compiler's
        own ``memory_analysis`` at two geometries by
        tests/test_tpu_compile.py, because the rule is the compiler's
        to change.  The module docstring's
        trap is the OTHER layout: small trailing dims kept logical
        (``(N, P, 6, 6, 7)``) tile to (8, 128) each."""
        def row(width, itemsize):
            w = _stored_width(max(int(width), 1))
            w = 1 << (w - 1).bit_length() if w < 8 else _round_up(w, 8)
            return w * itemsize

        P = len(col["players"])
        A = col["amask"].shape[-1]
        obs_bytes = riders = 0
        for leaf in jax.tree.leaves(col["obs"]):
            if _rides_steps(leaf):
                riders += 1
                continue
            width = int(np.prod(leaf.shape[1:]))  # (T, P, ...) -> P*...
            item = (np.dtype(self.obs_store).itemsize
                    if np.issubdtype(leaf.dtype, np.floating)
                    else leaf.dtype.itemsize)
            obs_bytes += row(width, item)
        step = obs_bytes + row(_steps_width(P, A, riders), 4)
        return step * self.t_max + self._slot_const_bytes(P)

    @staticmethod
    def _slot_const_bytes(P):
        # per-slot channels: outcome (CAP, P, 1) f32 + ep_len/ep_total
        # i32, slots on the lane axis (measured ~35 B a slot at P=4,
        # allocator rounding included: double the logical bytes)
        return 2 * (P * 4 + 8)

    def _plan_buffers(self, col):
        """Latch the ring geometry from the first episode's columnar
        form and return the buffers' pytree of ``ShapeDtypeStruct`` —
        everything the jits need to trace, with nothing allocated (so
        the step programs can be compiled for a device that is only
        described: tests/test_tpu_compile.py)."""
        self.num_players = len(col["players"])
        per_slot = self._per_slot_bytes(col)
        # remembered for re-clamping when T_max grows
        self._per_step_bytes = (
            per_slot - self._slot_const_bytes(self.num_players)
        ) // self.t_max
        # the budget is a hard ceiling — flooring it away would OOM at
        # exactly the episode sizes (GRF-scale) where it matters most
        fit = max(1, self.max_bytes // per_slot)
        if fit < self.capacity:
            print(f"device replay: {self.capacity} episodes at "
                  f"~{per_slot/1e6:.2f} MB each exceed the "
                  f"{self.max_bytes >> 20} MiB budget; ring capped at "
                  f"{fit} (raise device_replay_mb to widen)"
                  + (" — WARNING: a ring this small cripples replay "
                     "diversity" if fit < 64 else ""))
            self.capacity = int(fit)
        P = self.num_players
        A = col["amask"].shape[-1]
        # + one scratch stripe past the ring (and one scratch slot)
        # where batched-append PADDING rows land; gathers never read it
        flat = self.capacity * self.t_max + self.run_round
        # logical per-step shapes; stored flattened to 2D (see module
        # docstring: TPU tile padding on small trailing dims)
        leaves = jax.tree.leaves(col["obs"])
        self.obs_shapes = [leaf.shape[1:] for leaf in leaves]
        self.obs_treedef = jax.tree.structure(col["obs"])
        # leaves that ride the steps channel have no buffer of their own
        self.obs_rides = [_rides_steps(leaf) for leaf in leaves]
        self.num_actions = A

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))

        def flat2d(shape, dtype):
            width = int(np.prod(shape)) if shape else 1
            return spec((flat, _stored_width(width)), dtype)

        return {
            "obs": self._own_channels(
                lambda a: flat2d(a.shape[1:],
                                 self.obs_store
                                 if np.issubdtype(a.dtype, np.floating)
                                 else a.dtype),
                col["obs"]),
            "steps": flat2d(
                (_steps_width(P, A, sum(self.obs_rides)),), jnp.int32),
            "outcome": spec((self.capacity + 1, P, 1), jnp.float32),
            "ep_len": spec((self.capacity + 1,), jnp.int32),
            "ep_total": spec((self.capacity + 1,), jnp.int32),
        }

    def _own_channels(self, fn, obs):
        """``fn`` over the observation leaves that have a ring channel
        of their own; one that rides ``steps`` leaves an empty place."""
        return jax.tree.unflatten(self.obs_treedef, [
            None if rides else fn(leaf)
            for leaf, rides in zip(jax.tree.leaves(obs), self.obs_rides)])

    def _init_buffers(self, col):
        self.buffers = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self._plan_buffers(col))
        if self._rep is not None:
            self.buffers = jax.device_put(self.buffers, self._rep)
        self.ep_len = np.zeros(self.capacity, np.int32)
        self._build_jits()

    def _build_jits(self):
        def append(buffers, ep, flat_idx, slots):
            # scatter write: per-step channels land at explicit flat
            # row indices (bucket-rounded episode rows + scratch-bound
            # padding), per-slot channels at their slot indices.  One
            # dispatch per ingest batch; shapes bucket to _RUN_ROUND
            # totals so the jit compiles a handful of variants.
            out = {}
            for key, buf in buffers.items():
                idx = slots if key in _PER_SLOT else flat_idx
                out[key] = jax.tree.map(
                    lambda b, e, i=idx: b.at[i].set(e),
                    buf, ep[key])
            return out

        if self._rep is not None:
            self._append_fn = jax.jit(
                append, donate_argnums=0, out_shardings=self._rep)
            self._sample_fn = jax.jit(
                self._gather_batch, out_shardings=self._out)
        else:
            self._append_fn = jax.jit(append, donate_argnums=0)
            self._sample_fn = jax.jit(self._gather_batch)

    def _pad_episode(self, col, rows):
        """Columnar episode -> (rows, ...) host arrays in the storage
        dtypes (``rows`` is the episode's bucket-rounded length, NOT
        t_max: short episodes must not ship full stripes)."""
        T = len(col["turn_idx"])
        pad = rows - T

        def padt(a):
            a = np.ascontiguousarray(a).reshape(T, -1)  # 2D storage
            lanes = _stored_width(a.shape[1]) - a.shape[1]
            if pad == 0 and lanes == 0:
                return a
            return np.pad(a, [(0, pad), (0, lanes)])

        def obs_store(a):
            if not np.issubdtype(a.dtype, np.floating):
                return a
            if self.obs_store == np.uint8:
                q = a.astype(np.uint8)
                if not np.array_equal(q.astype(a.dtype), a):
                    raise ValueError(
                        "transfer_dtype 'uint8' requires integer-"
                        "valued observations; use 'bfloat16'")
                return q
            return a.astype(self.obs_store)

        return {
            "obs": self._own_channels(
                lambda a: padt(obs_store(a)), col["obs"]),
            "steps": padt(_pack_steps(col)),
            "outcome": col["outcome"][None],  # (1, P, 1): one ring slot
            "ep_len": np.asarray([T], np.int32),
            "ep_total": np.asarray([col["steps"]], np.int32),
        }

    def _append_run(self, cols):
        """Write ``len(cols) <= _MAX_RUN`` episodes with ONE device
        scatter.  Each episode ships its bucket-rounded rows; the
        batch's total rows pad to _RUN_ROUND (padding rows scatter
        into the scratch stripe past the ring, per-slot padding into
        the scratch slot) so the jit sees few shapes.  Callers
        guarantee buffers exist and no episode exceeds t_max; slot
        wrap-around needs no special casing — indices are explicit."""
        k = len(cols)
        with _telemetry.trace_span("ingest.pad"):
            lens = [len(c["turn_idx"]) for c in cols]
            rows = [_round_up(t) for t in lens]
            eps = []
            for c, r in zip(cols, rows):
                eps.append(self._pad_episode(c, r))
                self.inflight.poll("ingest.pad")
            slots = [(self.write_ptr + i) % self.capacity
                     for i in range(k)]
            total = sum(rows)
            pad = -total % self.run_round
            scratch = self.capacity * self.t_max
            flat_idx = np.concatenate(
                [s * self.t_max + np.arange(r, dtype=np.int32)
                 for s, r in zip(slots, rows)]
                + ([scratch + np.arange(pad, dtype=np.int32)]
                   if pad else []))
            slot_idx = np.asarray(
                slots + [self.capacity] * (self.max_run - k), np.int32)

            def cat_steps(*arrs):
                out = np.concatenate(arrs)
                if pad:
                    out = np.concatenate(
                        [out,
                         np.zeros((pad,) + out.shape[1:], out.dtype)])
                return out

            def cat_slots(*arrs):
                out = np.concatenate(arrs)
                if k < self.max_run:
                    out = np.concatenate([out, np.zeros(
                        (self.max_run - k,) + out.shape[1:], out.dtype)])
                return out

            ep = {key: jax.tree.map(
                cat_slots if key in _PER_SLOT else cat_steps,
                *[e[key] for e in eps]) for key in eps[0]}
        with _telemetry.trace_span("ingest.append") as span, \
                self.inflight.watch("ingest.append", span.attrs):
            # the dispatch and the implicit upload of ``ep``, and the
            # wait wherever the runtime's queue is full of steps: the
            # span's ``depth`` and ``done`` tell a held call apart
            self.buffers = self._append_fn(
                self.buffers, ep, flat_idx, slot_idx)
            for s, t in zip(slots, lens):
                self.ep_len[s] = t
            self.write_ptr = (self.write_ptr + k) % self.capacity
            self.size = min(self.size + k, self.capacity)
            self.episodes_seen += k
            self._state_dirty = True
            if _telemetry.enabled():
                # offer -> drawable, per episode of the run (an episode
                # that came by another road than ``offer`` has no stamp)
                now = _telemetry.now()
                span.attrs["wait_ms"] = [
                    round(1e3 * (now - c["offered_at"]), 3)
                    for c in cols if "offered_at" in c]

    def _grow(self, new_t_max):
        """A longer episode than ever seen arrived: re-lay the ring
        with a larger T_max (device-side copy + one recompile).  Growth
        doubles, so this happens O(log T) times per run.  The byte
        budget is re-enforced: if wider slots no longer fit, the ring
        shrinks, keeping the NEWEST episodes (FIFO semantics)."""
        old_t, cap = self.t_max, self.capacity
        per_slot_const = self._slot_const_bytes(self.num_players)
        new_cap = min(cap, max(1, self.max_bytes // (
            self._per_step_bytes * new_t_max + per_slot_const)))
        print(f"device replay: growing T_max {old_t} -> {new_t_max}"
              + (f", ring {cap} -> {new_cap} (byte budget)"
                 if new_cap < cap else ""))

        # slot order oldest -> newest, truncated to the newest new_cap
        n = self.size
        order = [(self.write_ptr - n + i) % cap for i in range(n)]
        keep = np.asarray(order[-new_cap:] if n > new_cap else order,
                          np.int32)
        kept = len(keep)
        # per-step channels gather whole slot stripes via flat indices
        flat_keep = (keep[:, None] * old_t
                     + np.arange(old_t)[None]).reshape(-1)

        def relayout(buf):
            def leaf(a):
                if a.shape[0] == cap * old_t + self.run_round:
                    rows = a[flat_keep].reshape(
                        (kept, old_t) + a.shape[1:])
                    pad = [(0, new_cap - kept), (0, new_t_max - old_t)
                           ] + [(0, 0)] * (a.ndim - 1)
                    flat = jnp.pad(rows, pad).reshape(
                        (new_cap * new_t_max,) + a.shape[1:])
                    # fresh scratch stripe past the new ring
                    return jnp.pad(
                        flat,
                        [(0, self.run_round)] + [(0, 0)] * (a.ndim - 1))
                # per-slot channel (+ its scratch slot)
                rows = a[keep]
                pad = [(0, new_cap + 1 - kept)] + [(0, 0)] * (a.ndim - 1)
                return jnp.pad(rows, pad)
            return tree_map(leaf, buf)

        # jaxlint: disable=retrace-risk -- growth doubles T_max, so this compiles O(log T) times per run and the shapes differ every time anyway
        self.buffers = jax.jit(
            relayout, donate_argnums=0, out_shardings=self._rep
        )(self.buffers)
        new_len = np.zeros(new_cap, np.int32)
        new_len[:kept] = self.ep_len[keep]
        self.ep_len = new_len
        self.size = kept
        self.write_ptr = kept % new_cap
        self.capacity = new_cap
        self.t_max = new_t_max
        self.growths += 1
        self._state_dirty = True
        self._build_jits()

    # -- sampling -----------------------------------------------------

    @property
    def oldest(self):
        """Ring slot of the oldest live episode (host mirror)."""
        return (self.write_ptr - self.size) % self.capacity

    def draw_indices(self, batch_size):
        """Host-side draw: recency-biased episode choice + random
        training window, as three int32 vectors.

        Same distribution as Batcher.select_episode's accept loop —
        P(idx) = (idx+1)/S with S = n(n+1)/2 — but drawn in closed
        form (inverse CDF of the discrete triangle) so a 256-row draw
        is a few numpy ops, not 256 Python rejection loops."""
        if self._rng is None:
            self._rng = np.random.default_rng(random.getrandbits(64))
        rng = self._rng
        n = self.size
        oldest = self.oldest
        # (idx+1)(idx+2) <= u*n*(n+1) + 2  =>  triangular idx
        u = rng.random(batch_size)
        idx = np.floor(
            (np.sqrt(1.0 + 4.0 * u * n * (n + 1)) - 3.0) / 2.0
        ).astype(np.int64) + 1
        idx = np.clip(idx, 0, n - 1)
        slots = ((oldest + idx) % self.capacity).astype(np.int32)
        cands = 1 + np.maximum(0, self.ep_len[slots] - self.forward_steps)
        tstarts = rng.integers(0, cands, dtype=np.int32)
        if self.mode == "seat":
            seats = rng.integers(
                0, self.num_players, batch_size, dtype=np.int32)
        else:
            seats = np.zeros(batch_size, np.int32)
        return slots, tstarts, seats

    def sample(self, batch_size):
        """One device-resident training batch (trainer thread only)."""
        slots, tstarts, seats = self.draw_indices(batch_size)
        return self._sample_fn(
            self.buffers, jnp.asarray(slots), jnp.asarray(tstarts),
            jnp.asarray(seats))

    def _draw_on_device(self, buffers, size, oldest, step_idx,
                        base_key, batch_size):
        """The draw_indices math as traced jax ops (used inside the
        fused update step, so a step needs no per-call array uploads).
        Same distributions as the host draw — triangular recency over
        the ring, uniform window start, uniform seat — on a different
        RNG stream (jax PRNG keyed by the config seed + step counter;
        like the host path, which draws from the ``random`` module the
        Learner seeds with ``args['seed']``, the stream is
        config-seed-deterministic)."""
        key = jax.random.fold_in(base_key, step_idx)
        k1, k2, k3 = jax.random.split(key, 3)
        size = jnp.asarray(size)
        n = size.astype(jnp.float32)
        u = jax.random.uniform(k1, (batch_size,))
        idx = jnp.floor(
            (jnp.sqrt(1.0 + 4.0 * u * n * (n + 1)) - 3.0) / 2.0
        ).astype(jnp.int32) + 1
        idx = jnp.clip(idx, 0, size - 1)
        slots = (oldest + idx) % self.capacity
        cands = 1 + jnp.maximum(
            0, buffers["ep_len"][slots] - self.forward_steps)
        tstarts = jnp.floor(
            jax.random.uniform(k2, (batch_size,)) * cands
        ).astype(jnp.int32)
        if self.mode == "seat":
            seats = jax.random.randint(
                k3, (batch_size,), 0, self.num_players, jnp.int32)
        else:
            seats = jnp.zeros(batch_size, jnp.int32)
        return slots, tstarts, seats

    # The gather: all of make_batch's semantics, on device.
    def _gather_batch(self, buffers, slots, tstarts, seats):
        t_max, t_win = self.t_max, self.t_win
        lens = buffers["ep_len"][slots]                  # (B,)
        totals = buffers["ep_total"][slots]

        # window positions g in episode time; validity from lengths
        g = (tstarts - self.burn_in)[:, None] + jnp.arange(t_win)  # (B,T)
        valid = (g >= 0) & (g < lens[:, None])
        after = g >= lens[:, None]       # past the terminal step
        gi = jnp.clip(g, 0, t_max - 1)
        flat_idx = slots[:, None] * t_max + gi                     # (B,T)

        def fetch(buf, shape):
            # 2D ring row -> logical (B, T, *shape) window (the lane
            # padding of _stored_width stays behind)
            width = int(np.prod(shape)) if shape else 1
            return buf[flat_idx][..., :width].reshape(
                flat_idx.shape + tuple(shape))

        def mask_t(x, pad_value, m=valid):
            shape = m.shape + (1,) * (x.ndim - 2)
            return jnp.where(m.reshape(shape), x, pad_value)

        def fetch_seat(buf, shape):
            # seat mode keeps ONE seat of an observation: its columns
            # are chosen on the gathered 2D rows, so only that seat's
            # share is re-laid to the logical (B, T, 1, ...) shape
            per = int(np.prod(shape[1:]))
            rows = buf[flat_idx]
            seat = seats[:, None, None]
            sel = rows[..., :per]
            for p in range(1, shape[0]):
                sel = jnp.where(seat == p,
                                rows[..., p * per:(p + 1) * per], sel)
            return sel.reshape(flat_idx.shape + (1,) + tuple(shape[1:]))

        # the per-step scalars' row, taken apart (_pack_steps)
        P, A = self.num_players, self.num_actions
        steps = buffers["steps"][flat_idx]               # (B,T,5P+1+W)

        def column(i, dtype=jnp.float32):
            x = steps[..., i * P:(i + 1) * P, None]      # (B,T,P,1)
            return jax.lax.bitcast_convert_type(x, dtype)

        prob, act = column(0), column(1, jnp.int32)
        value, reward, ret = column(2), column(3), column(4)
        turn = steps[..., 5 * P]                         # (B,T)
        riders = sum(self.obs_rides)
        words = steps[..., (5 + riders) * P + 1:, None]
        bits = ((words >> jnp.arange(32)) & 1).reshape(
            flat_idx.shape + (-1,)) != 0
        omask = bits[..., :P, None]
        tmask = bits[..., P:2 * P, None]
        amask = bits[..., 2 * P:P * (A + 2)].reshape(
            flat_idx.shape + (P, A))
        own = iter(jax.tree.leaves(buffers["obs"]))
        rode = iter(range(riders))
        obs = []
        for shape, rides in zip(self.obs_shapes, self.obs_rides):
            if rides:
                at = 5 * P + 1 + next(rode) * P
                leaf = steps[..., at:at + P]             # (B,T,P)
                if self.mode == "seat":
                    leaf = jnp.take_along_axis(
                        leaf, seats[:, None, None], axis=2)
            else:
                leaf = (fetch_seat if self.mode == "seat"
                        else fetch)(next(own), shape)
            obs.append(leaf)
        obs = jax.tree.unflatten(self.obs_treedef, obs)
        #                                 (B,T,P,...); seat: (B,T,1,...)
        outcome = buffers["outcome"][slots]              # (B,P,1)

        def select_players(x, idx):
            # (B,T,P,...) -> (B,T,1,...) by per-(row,step) player index
            shape = idx.shape + (1,) * (x.ndim - 2)
            return jnp.take_along_axis(
                x, idx.reshape(shape).astype(jnp.int32), axis=2)

        if self.mode == "turn":
            def acting(x):
                return select_players(x, turn)
        elif self.mode == "seat":
            seat_bt = jnp.broadcast_to(seats[:, None], turn.shape)

            def acting(x):
                return select_players(x, seat_bt)

            # seat mode selects ONE player for every channel
            value, reward, ret = acting(value), acting(reward), acting(ret)
            tmask, omask = acting(tmask), acting(omask)
            outcome = jnp.take_along_axis(
                outcome, seats[:, None, None], axis=1)
        else:
            def acting(x):
                return x

        cdt = jnp.dtype(self.compute_dtype)

        def obs_out(a):
            sel = a if self.mode == "seat" else acting(a)
            if (jnp.issubdtype(sel.dtype, jnp.floating)
                    or sel.dtype == jnp.uint8):
                sel = sel.astype(cdt)
            return mask_t(sel, 0)

        return {
            "observation": tree_map(obs_out, obs),
            "selected_prob": mask_t(acting(prob), 1.0),
            "action": mask_t(acting(act), 0),
            "action_mask": jnp.where(
                mask_t(acting(amask), True),
                jnp.float32(ILLEGAL), jnp.float32(0)),
            "value": jnp.where(
                after[..., None, None],
                outcome[:, None],
                mask_t(value, 0.0)),
            "reward": mask_t(reward, 0.0),
            "return": mask_t(ret, 0.0),
            "outcome": outcome[:, None],                 # (B,1,P,1)
            "episode_mask": valid[..., None, None].astype(jnp.float32),
            "turn_mask": mask_t(tmask, False).astype(jnp.float32),
            "observation_mask": mask_t(omask, False).astype(jnp.float32),
            "progress": (jnp.where(
                valid,
                g.astype(jnp.float32) / totals[:, None].astype(
                    jnp.float32),
                1.0))[..., None],
        }
