"""Batched inference service: the learner-side half of the pipeline.

One server thread owns a snapshot of the model and answers obs->action
requests from every attached rollout worker: requests accumulate
across workers inside a **wait-or-timeout batching window**
(``pipeline.batch_window`` seconds after the first pending request, or
until ``pipeline.max_batch`` rows are staged, whichever first), then
ONE jitted ``inference_batch`` forward covers all of them and replies
scatter back over each worker's reply ring.  This replaces the
per-worker ``ModelWrapper.inference`` hot path (Sebulba, Podracer
arXiv:2104.06272; SEED-style centralized inference, IMPALA) — actor
processes become env-stepping loops that enqueue observations and
block on actions.

Snapshot **hot swap**: the learner hands every new epoch's model to
``set_model``; the loop adopts it between batches, re-pointing the
compiled forward at the new params (the trace is weight-independent,
so no recompile) — in-flight requests are never dropped, they are
simply answered by whichever snapshot is installed when their batch
dispatches (importance corrections stay exact: workers record the
behavior probabilities the reply actually carried).

Batch shapes bucket to powers of two (floor 8, ceiling ``max_batch``)
so XLA compiles a handful of variants instead of one per request mix.

Liveness is a heartbeat stamp on a shared ``ShmBoard``: workers watch
its age and fall back to local CPU inference when the service goes
silent (death is a supervised, chaos-injectable fault — the learner
respawns the thread and workers return on their own once the beat
resumes).

Telemetry: every dispatch records an ``infer.batch`` span (rows,
window wait), and ``epoch_stats`` reduces the epoch's dispatches into
``infer_batch_size_{mean,p95}`` / ``infer_queue_wait_sec`` /
``shm_ring_full_count`` for metrics.jsonl (docs/observability.md).

**Two planes, one window** (docs/serving.md): besides the shm rings,
``submit`` queues NETWORK-plane requests (the serving frontend's
handler threads call it) into the same batching window — a remote
client's rows and a colocated worker's rows ride one bucket-padded
jitted forward.  A network request may carry an **epoch pin**:
``_routed`` resolves it through ``model_resolver`` (set by the
learner) so league/opponent-pool snapshots are first-class serving
targets — pinned seats get the snapshot they asked for instead of an
error or the live model, and since params are jit *arguments* a routed
snapshot shares the live model's compiled forward (no recompile).

**GSPMD dispatch** (ROADMAP item 2): with a ``mesh`` the service owns
ONE jitted forward built with ``in_shardings``/``out_shardings`` from
:func:`parallel.mesh.inference_shardings` — params laid out by the
learner's tp/fsdp rules (nets too big for one chip become servable),
the observation batch split over ``dp`` rows, outputs scattered back
on ``dp``.  Params stay jit *arguments*: each snapshot (live or
routed) is ``device_put`` onto the param shardings ONCE and cached on
the model object, so hot-swap and multi-model routing never pay a
per-request reshard.  The dispatch rides the same guard contract as
the update step: a :class:`analysis.guards.ShardingContractGuard`
counts resharding copies (``infer_resharding_copies`` in
metrics.jsonl, steady state 0) and a RetraceGuard counts compiles
(``infer_compiles`` — exactly one per batch-bucket geometry, however
many snapshots serve through it).  A single-device mesh (or no mesh)
collapses to the unsharded layout bit-identically; batch buckets stay
powers of two with a floor >= dp so every dispatch divides the data
axis.
"""

import threading
import time
from collections import deque

from .. import telemetry
from .shm import (
    ShmBoard,
    ShmRing,
    dumps,
    loads_view,
    unpack_request,
)


class _Client:
    """One attached worker: its three rings + request schema."""

    __slots__ = ("cid", "req", "rsp", "traj", "leaf_specs", "example",
                 "rows_max", "treedef", "req_stuck_since",
                 "traj_stuck_since", "last_seen", "drop_warned")

    def __init__(self, cid, req, rsp, traj, leaf_specs, example,
                 rows_max):
        self.cid = cid
        self.req = req
        self.rsp = rsp
        self.traj = traj
        self.leaf_specs = [(tuple(s), str(d)) for s, d in leaf_specs]
        self.example = example
        self.rows_max = rows_max
        self.treedef = None          # resolved lazily (jax import)
        self.req_stuck_since = None  # torn-write reclaim bookkeeping
        self.traj_stuck_since = None
        self.last_seen = 0.0         # last request/trajectory activity
        self.drop_warned = False     # reply-drop warning, once per client

    def deliver(self, seq, epoch, part) -> bool:
        """Hand one answered request back over the reply ring.  The
        network-plane seat (serving frontend) implements the same
        method by waking its handler thread — dispatch is polymorphic
        over the two planes."""
        if part is None:
            return True  # shm requests are never epoch-pinned
        return self.rsp.push(dumps((seq, epoch, part)))


def _bucket(n, cap, floor=8):
    """Pad target for an n-row batch: next power of two, floor
    ``floor`` (8, or the mesh dp size when larger), ceiling ``cap`` —
    a handful of compiled shapes total, every one divisible by dp."""
    b = floor
    while b < n:
        b <<= 1
    return min(b, cap)


class InferenceService:
    """The batched inference server (one per learner process).

    Thread contract: ``attach``/``set_model``/``inject_kill``/``stats``
    may be called from the learner's server thread; the batching loop
    runs on the service's own thread; ``drain_trajectories`` belongs to
    the learner server thread (it is the trajectory rings' single
    consumer).  ``clock``/``sleep`` are injectable so the batching
    window is unit-testable without wall time.
    """

    TORN_GRACE = 30.0  # seconds a mid-write slot may stall before reclaim
    # a client silent on BOTH rings this long is presumed dead (its
    # worker crashed or degraded to pure-local) and its rings are
    # reclaimed; a live worker that gets reaped by mistake degrades
    # itself to local inference on the next reply timeout — degraded,
    # never wrong
    CLIENT_IDLE_REAP = 600.0
    GRAVE_GRACE = 10.0  # close only after in-flight snapshots expire

    def __init__(self, model, cfg, epoch=0, clock=time.monotonic,
                 sleep=time.sleep, chaos=None, mesh=None, fsdp=False,
                 max_reshard=0):
        import random

        from ..analysis.guards import RetraceGuard, ShardingContractGuard
        from ..resilience.chaos import maybe_chaos_board

        self.cfg = cfg
        # GSPMD dispatch (module docstring): the learner passes its
        # training mesh so one sharded program serves all planes.  The
        # pow2 bucket floor must divide by dp so every dispatch splits
        # the data axis evenly — a dp the buckets cannot honor disarms
        # the mesh LOUDLY (unsharded dispatch, never a trace error)
        self._mesh = None
        self._fsdp = bool(fsdp)
        self._bucket_floor = 8
        if mesh is not None:
            dp = int(mesh.shape["dp"]) or 1
            floor = self._bucket_floor
            if dp > floor and dp & (dp - 1) == 0:
                floor = dp  # pow2 dp above the floor: raise the floor
            # every bucket value the dispatch can produce — the pow2
            # ladder from the floor, clamped at max_batch — must
            # divide by dp (oversized chunks pad to a full pow2)
            if (floor % dp == 0 and int(cfg.max_batch) % dp == 0
                    and floor <= int(cfg.max_batch)):
                self._mesh = mesh
                self._bucket_floor = floor
            else:
                print(f"WARNING: inference mesh disarmed: dp={dp} "
                      f"does not divide the pow2 batch buckets "
                      f"(floor {floor}, max_batch {cfg.max_batch}); "
                      f"inference dispatch runs unsharded")
        # guard contract, same as the update step's: compiles counted
        # per abstract geometry (one per batch bucket, NOT per
        # snapshot), resharding copies at the call boundary budgeted
        # at copies == 0 steady state (max_reshard > 0 hard-asserts)
        self.retrace_guard = RetraceGuard(name="inference_batch")
        self.shard_guard = ShardingContractGuard(
            max_copies=int(max_reshard or 0), name="inference_batch")
        self._fwd = None           # the service-owned guarded jit
        self._fwd_module = None    # the module it was traced for
        self._infer_sh = None      # InferenceShardings when mesh-armed
        self.clock = clock
        self.sleep = sleep
        self._lock = threading.Lock()
        self._clients = {}
        self._next_cid = 0
        self._model = model
        self._epoch = int(epoch)
        self._pending_model = None
        # shm chaos (resilience.ChaosRing/ChaosBoard): this side
        # produces replies and consumes requests/trajectories, and its
        # heartbeat can be withheld/backdated — all seeded off the one
        # chaos RNG discipline so drills replay exactly
        self._chaos = chaos if (chaos is not None
                                and (chaos.shm_faults_enabled
                                     or chaos.shm_beat_faults_enabled)
                                ) else None
        self._chaos_rng = (
            random.Random((chaos.seed << 20) ^ 0xB0A2)
            if self._chaos is not None else None)
        self.board = maybe_chaos_board(
            ShmBoard.create(), self._chaos, rng=self._chaos_rng)
        self._thread = None
        self._stop = False
        self._kill = False           # chaos: die WITHOUT a parting beat
        # network plane (handyrl_tpu.serving): frontend handler
        # threads queue requests here via submit(); _collect drains
        # them into the same batching window as the shm rings.  The
        # queue belongs to this OBJECT, not the loop thread, so
        # requests queued across a chaos kill are served by the
        # respawned incarnation instead of dying with the thread
        self._net_pending = deque()
        # epoch pin -> model, set by the learner (multi-model routing:
        # league/opponent-pool snapshots as serving targets); None
        # makes every non-live pin unroutable (typed error upstream)
        self.model_resolver = None
        self.net_requests = 0        # cumulative network-plane frames
        # counters — epoch accumulators reset by epoch_stats()
        self._batch_rows = []
        self._queue_wait = 0.0
        self._requests_epoch = 0
        self._warm = []              # client ids awaiting a jit warmup
        self.batches = 0             # cumulative dispatches
        self.requests = 0            # cumulative request frames served
        self.rows_served = 0         # cumulative obs rows answered
        self.reclaimed = 0           # torn slots skipped (dead writers)
        self.corrupt = 0             # undecodable slots skipped
        self.reply_drops = 0         # replies refused by a full/small ring
        self.reaped = 0              # idle clients reclaimed
        self._grave = []             # (deadline, client) pending close

    # -- control-plane face (learner server thread) --------------------
    def attach(self, spec):
        """Allocate a client slot + rings for one worker's handshake
        (verb ``"shm"``); returns the attach descriptor the worker
        maps, or raises on a malformed spec (the learner's handler
        answers None for refusals — remote peers, shutdown)."""
        leaf_specs = spec["leaves"]
        rows_max = max(1, int(spec.get("rows_max", 1)))
        import numpy as np

        row_bytes = sum(
            int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
            for shape, dtype in leaf_specs)
        need = 16 + 2 * rows_max * max(1, row_bytes)
        slot = max(int(self.cfg.slot_bytes), need)
        from ..resilience.chaos import maybe_chaos_ring

        with self._lock:
            cid = self._next_cid
            self._next_cid += 1

            def ring(*a):
                # service-side chaos endpoint: reply pushes can tear/
                # truncate/refuse, request/trajectory pops can stall
                return maybe_chaos_ring(
                    ShmRing.create(*a), self._chaos, rng=self._chaos_rng)

            client = _Client(
                cid,
                req=ring(self.cfg.ring_slots, slot),
                rsp=ring(self.cfg.ring_slots, slot),
                traj=ring(self.cfg.traj_slots,
                          int(self.cfg.traj_slot_mb) << 20),
                leaf_specs=leaf_specs,
                example=spec["example"],
                rows_max=rows_max,
            )
            client.last_seen = self.clock()
            self._clients[cid] = client
            # warm this schema's buckets from the SERVICE thread (the
            # handshake/model-fetch slack), so the first real request
            # is not the one paying the jit compile — a compile longer
            # than fallback_after would bounce it to local fallback
            self._warm.append(cid)
        return {
            "client": cid,
            "board": self.board.name,
            "req": client.req.descriptor(),
            "rsp": client.rsp.descriptor(),
            "traj": client.traj.descriptor(),
        }

    def set_model(self, model, epoch):
        """Hot-swap the serving snapshot; adopted between batches, so
        no in-flight request is ever dropped."""
        with self._lock:
            self._pending_model = (model, int(epoch))

    # -- network plane (serving frontend handler threads) --------------
    def submit(self, seat, seq, rows, leaves, epoch=None) -> bool:
        """Queue one network-plane request into the batching window.
        ``seat`` is the frontend's client duck type (``example`` /
        ``treedef`` / ``deliver``); ``epoch`` pins the request to a
        specific snapshot (None = the live model).  False = the
        service is shut down for good (the frontend sheds with a typed
        reply).  A merely-dead (killed, pre-respawn) service still
        accepts: the queue belongs to the object, so these requests
        are served by the respawned incarnation — the frontend's
        admission check (``service.alive``) is what sheds NEW arrivals
        during the gap."""
        if self._stop:
            return False
        with self._lock:
            self._net_pending.append(
                (seat, seq, int(rows), leaves,
                 None if epoch is None else int(epoch)))
        return True

    def inject_kill(self):
        """Chaos: the loop exits without a parting beat — exactly what
        a SIGKILLed dedicated server process would look like to the
        workers (stale board) and the learner (dead thread)."""
        self._kill = True

    @property
    def alive(self):
        return self._thread is not None and self._thread.is_alive()

    def start(self):
        self._kill = False
        self._stop = False
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="infer-service")
        self._thread.start()

    def respawn(self):
        """Relaunch after a death: same rings, same clients — state
        lives in shared memory, so workers resume on their own once
        the beat returns (a fresh generation stamp says it's a new
        incarnation)."""
        self.board.bump_generation()
        self.start()

    def stop(self):
        self._stop = True
        if self._thread is not None:
            self._thread.join(timeout=5)

    def close(self):
        self.stop()
        with self._lock:
            clients = list(self._clients.values())
            self._clients.clear()
            # the graveyard holds reaped clients whose GRAVE_GRACE has
            # not expired; final teardown must not strand their rings
            # (3 shm segments each) waiting for a reaper that is gone
            clients.extend(c for _due, c in self._grave)
            self._grave = []
        for c in clients:
            c.req.close()
            c.rsp.close()
            c.traj.close()
        self.board.close()

    # -- metrics -------------------------------------------------------
    def ring_full_count(self):
        """Cumulative push refusals across every ring of every client,
        read straight from the shm headers — includes the counts the
        WORKERS' producer sides maintained (req/traj rings), with no
        control-plane reporting needed."""
        total = 0
        with self._lock:
            clients = list(self._clients.values())
        for c in clients:
            total += (c.req.full_count + c.rsp.full_count
                      + c.traj.full_count)
        return total

    def torn_slot_count(self):
        """Cumulative torn/corrupt slots skipped across every ring of
        every client — the consumer-side skip counters live in the shm
        headers, so this covers the WORKERS' reply-ring skips too (no
        control-plane reporting needed), plus this side's reclaims."""
        total = 0
        with self._lock:
            clients = list(self._clients.values())
        for c in clients:
            total += (c.req.torn_count + c.rsp.torn_count
                      + c.traj.torn_count)
        return total

    def epoch_stats(self):
        """Per-epoch reduction for metrics.jsonl; resets the epoch
        accumulators.  Keys are the docs/observability.md contract."""
        with self._lock:
            rows = self._batch_rows
            wait = self._queue_wait
            requests = self._requests_epoch
            self._batch_rows = []
            self._queue_wait = 0.0
            self._requests_epoch = 0
        out = {
            "infer_batches": len(rows),
            "infer_requests": requests,
            # the dispatch's guard contract (module docstring): copies
            # is a per-epoch delta whose steady state is 0 — any
            # positive count means a snapshot landed on the wrong
            # layout and every forward pays a silent copy; compiles is
            # cumulative and stops growing once every bucket geometry
            # has compiled (snapshots never add one)
            "infer_resharding_copies": self.shard_guard.snapshot(),
            "infer_compiles": self.retrace_guard.compiles,
            "shm_ring_full_count": self.ring_full_count(),
            # torn/corrupt slots skipped, cumulative, read from the
            # shm headers (covers both endpoints' skips).  Steady
            # state is flat at 0; a climbing line means producers are
            # dying mid-write (or payloads are corrupting) faster
            # than the fleet's churn explains
            "shm_torn_slots": self.torn_slot_count(),
        }
        if rows:
            srt = sorted(rows)
            out["infer_batch_size_mean"] = round(
                sum(rows) / len(rows), 2)
            out["infer_batch_size_p95"] = srt[
                min(len(srt) - 1, int(0.95 * len(srt)))]
            out["infer_queue_wait_sec"] = round(wait / len(rows), 6)
        return out

    def stats(self):
        """Cumulative snapshot (status endpoint)."""
        with self._lock:
            n = len(self._clients)
        return {
            "clients": n,
            "epoch": self._epoch,
            "alive": self.alive,
            "generation": self.board.generation,
            "batches": self.batches,
            "requests": self.requests,
            "net_requests": self.net_requests,
            "rows_served": self.rows_served,
            "shm_ring_full_count": self.ring_full_count(),
            "shm_torn_slots": self.torn_slot_count(),
            "torn_reclaimed": self.reclaimed,
            "corrupt_slots": self.corrupt,
            "reply_drops": self.reply_drops,
            "clients_reaped": self.reaped,
            "infer_resharding_copies": self.shard_guard.copies,
            "infer_compiles": self.retrace_guard.compiles,
            "mesh_devices": (int(self._mesh.size)
                             if self._mesh is not None else 1),
        }

    # -- trajectory intake (learner server thread) ---------------------
    def drain_trajectories(self, max_episodes=512):
        """Pop finished episodes off every client's trajectory ring —
        the learner feeds them straight into episode intake.  This
        thread is those rings' single consumer."""
        episodes = []
        now = self.clock()
        with self._lock:
            clients = list(self._clients.values())
        for c in clients:
            while len(episodes) < max_episodes:
                try:
                    ep = c.traj.pop(loads=loads_view)
                except Exception as exc:
                    self._skip_corrupt(c.traj, c.cid, "trajectory", exc)
                    continue
                if ep is None:
                    c.traj_stuck_since = self._maybe_reclaim(
                        c.traj, c.traj_stuck_since, now,
                        cid=c.cid, kind="trajectory")
                    break
                c.traj_stuck_since = None
                c.last_seen = now
                episodes.append(ep)
        return episodes

    def _skip_corrupt(self, ring, cid, kind, exc):
        """A slot whose seqlock stamp is complete but whose payload
        would not decode (truncation, bit rot): skip it LOUDLY — the
        slot is counted torn in the shm header and the ring flows
        again.  Crashing here would take the learner's server loop
        (and every client) down over one bad frame."""
        if ring.skip_one():
            # bumped from both the learner's drain thread and the
            # service loop — unlocked += on both would lose counts
            with self._lock:
                self.corrupt += 1
            print(f"WARNING: corrupt {kind} slot from client {cid} "
                  f"skipped ({exc!r})")

    def _maybe_reclaim(self, ring, stuck_since, now, cid=-1,
                       kind="request"):
        """Mid-write slot watch: a slot odd-stamped for longer than
        TORN_GRACE means its writer died mid-frame (a live writer
        finishes in microseconds) — skip it LOUDLY so the ring flows
        again.  Returns the updated stuck-since stamp."""
        if not ring.pending() or ring.readable():
            return None
        if stuck_since is None:
            return now
        if now - stuck_since >= self.TORN_GRACE:
            if ring.skip_torn():
                # same two-thread caller set as _skip_corrupt above
                with self._lock:
                    self.reclaimed += 1
                print(f"WARNING: torn {kind} slot from client {cid} "
                      f"reclaimed (writer dead mid-RESERVE-THEN-FILL, "
                      f"stalled {now - stuck_since:.0f}s); the ring "
                      f"flows again")
            return None
        return stuck_since

    # -- the batching loop --------------------------------------------
    def _adopt_model(self):
        with self._lock:
            pending = self._pending_model
            self._pending_model = None
        if pending is None:
            return
        model, epoch = pending
        # the compiled forward survives the swap in _ensure_forward
        # (the service-owned jit is cached by module EQUALITY and
        # params are jit arguments); duck models without a module
        # carry their own inference_batch and need no adoption
        self._model = model
        self._epoch = epoch

    def _obs_tree(self, client, leaves):
        import jax

        if client.treedef is None:
            client.treedef = jax.tree.structure(client.example)
        return jax.tree.unflatten(client.treedef, leaves)

    # -- the guarded (and, with a mesh, GSPMD) forward -----------------
    def _ensure_forward(self, model):
        """The service-owned jitted ``inference_batch``, built once per
        module and shared by every snapshot (params are jit arguments:
        hot-swap and routed dispatch reuse the trace).  None for duck
        models with no jittable ``module`` (they keep their own
        ``inference_batch``)."""
        module = getattr(model, "module", None)
        if module is None or not hasattr(module, "apply") \
                or getattr(model, "params", None) is None:
            return None  # RandomModel/stub ducks keep their own path
        if self._fwd is not None:
            prev = self._fwd_module
            try:
                if prev is module or prev == module:
                    return self._fwd
            except Exception:
                pass
        import jax
        import numpy as np

        def apply(params, obs):
            return module.apply({"params": params}, obs, None)

        if self._mesh is not None:
            from ..parallel.mesh import OrderedLaunch, inference_shardings

            self._infer_sh = inference_shardings(
                self._mesh, model.params, fsdp=self._fsdp)
            # the trainer's step runs over the same devices: launches
            # are ordered with it, one executable per batch bucket
            fwd = OrderedLaunch(
                jax.jit(apply,
                        in_shardings=(self._infer_sh.params,
                                      self._infer_sh.obs),
                        out_shardings=self._infer_sh.out),
                self._mesh,
                key=lambda args: np.shape(jax.tree.leaves(args[1])[0]))
        else:
            self._infer_sh = None
            fwd = jax.jit(apply)
        self._fwd = self.retrace_guard.wrap(self.shard_guard.wrap(fwd))
        self._fwd_module = module
        return self._fwd

    def _placed_params(self, model):
        """This snapshot's params on the inference param shardings —
        ``device_put`` ONCE per snapshot (live or routed), cached on
        the model object so the learner's LRU stores sharded pytrees
        and no dispatch ever pays a per-request reshard.  The cache is
        KEYED by the sharding set it was placed with: a snapshot that
        crosses services with different meshes (tests, dry runs)
        re-places instead of dispatching params committed to another
        mesh's layout."""
        if self._infer_sh is None:
            return model.params
        cached = getattr(model, "_infer_placed", None)
        if cached is not None and cached[0] is self._infer_sh:
            return cached[1]
        import jax

        placed = jax.device_put(model.params, self._infer_sh.params)
        try:
            model._infer_placed = (self._infer_sh, placed)
        except Exception:
            pass
        return placed

    def _forward(self, model, obs):
        """One batched forward: numpy leaves in, numpy dict out (the
        ``inference_batch`` contract), through the guarded jit."""
        fwd = self._ensure_forward(model)
        if fwd is None:
            return model.inference_batch(obs, None)
        import jax
        import numpy as np

        out = fwd(self._placed_params(model), obs)
        return jax.tree.map(np.asarray, out)

    def _collect(self, pending, now):
        """One sweep over every request ring plus the network-plane
        queue; appends (client, seq, rows, leaves, epoch_pin) tuples.
        Returns rows collected this sweep."""
        got = 0
        with self._lock:
            clients = list(self._clients.values())
            net = list(self._net_pending)
            self._net_pending.clear()
        for item in net:
            pending.append(item)
            got += item[2]
            self.net_requests += 1
        for c in clients:
            while True:
                try:
                    item = c.req.pop(
                        loads=lambda v, c=c: unpack_request(
                            v, c.leaf_specs))
                except Exception as exc:
                    self._skip_corrupt(c.req, c.cid, "request", exc)
                    continue
                if item is None:
                    c.req_stuck_since = self._maybe_reclaim(
                        c.req, c.req_stuck_since, now,
                        cid=c.cid, kind="request")
                    break
                c.req_stuck_since = None
                c.last_seen = self.clock()
                seq, rows, leaves = item
                pending.append((c, seq, rows, leaves, None))
                got += rows
        return got

    def step(self):
        """One batching-window pass: collect, wait-or-timeout, forward,
        reply.  Returns True when a batch dispatched (the loop idles
        briefly otherwise).  Synchronous and clock-injected: unit
        tests drive it directly, no thread."""
        pending = []
        total = self._collect(pending, self.clock())
        if not pending:
            return False
        t_first = self.clock()
        # wait-or-timeout: give batch-mates from other workers
        # batch_window seconds to arrive, unless the batch is full
        deadline = t_first + self.cfg.batch_window
        while total < self.cfg.max_batch:
            now = self.clock()
            if now >= deadline:
                break
            self.sleep(min(2e-4, deadline - now))
            total += self._collect(pending, self.clock())
        self._dispatch(pending, self.clock() - t_first)
        return True

    def _routed(self, pin):
        """(model, epoch) for one dispatch group.  None pins — and
        pins naming the live snapshot — serve the installed model;
        other pins resolve through ``model_resolver`` (multi-model
        routing: league/opponent-pool snapshots as first-class
        serving targets).  (None, pin) = unroutable, answered as a
        typed unavailable upstream."""
        if pin is None or int(pin) == self._epoch:
            return self._model, self._epoch
        if self.model_resolver is None:
            return None, int(pin)
        try:
            model = self.model_resolver(int(pin))
        except Exception as exc:  # a bad pin costs that request only
            print(f"WARNING: snapshot resolver failed for epoch "
                  f"{pin} ({exc!r})")
            model = None
        return model, int(pin)

    def _dispatch(self, pending, waited):
        import numpy as np

        self._adopt_model()
        # group by epoch pin: the unpinned/live group (the common
        # case — ALL shm traffic plus unpinned network requests) rides
        # one bucket-padded forward; each pinned group dispatches with
        # its routed snapshot's params through the SAME compiled
        # forward (params are jit arguments — no recompile).  A pin
        # naming the LIVE epoch normalizes into the unpinned group —
        # splitting identical-params traffic into two forwards would
        # re-pay exactly the per-dispatch overhead the shared window
        # exists to amortize
        groups = {}
        for item in pending:
            pin = item[4]
            if pin is not None and int(pin) == self._epoch:
                pin = None
            groups.setdefault(pin, []).append(item)
        for pin, items in groups.items():
            model, epoch = self._routed(pin)
            if model is None:
                # unroutable pin (pruned/never-committed epoch, no
                # resolver): typed unavailable, not a silent timeout
                for seat, seq, _n, _leaves, _pin in items:
                    seat.deliver(seq, None, None)
                continue
            # one forward per max_batch chunk (normally exactly one)
            i = 0
            while i < len(items):
                chunk, rows = [], 0
                while i < len(items) and (
                        rows + items[i][2] <= self.cfg.max_batch
                        or not chunk):
                    chunk.append(items[i])
                    rows += items[i][2]
                    i += 1
                t0 = telemetry.span_begin()
                cap = max(rows, self.cfg.max_batch)
                if self._mesh is not None and rows > self.cfg.max_batch:
                    # oversized chunk under a mesh: pad to the FULL
                    # pow2 instead of clamping at the raw row count,
                    # so the bucket keeps dividing the dp axis
                    cap = 1 << (rows - 1).bit_length()
                bucket = _bucket(rows, cap, self._bucket_floor)
                leaves = [np.concatenate(parts, axis=0) for parts in zip(
                    *[leaves for _, _, _, leaves, _ in chunk])]
                if bucket > rows:
                    leaves = [np.concatenate(
                        [leaf, np.zeros((bucket - rows,) + leaf.shape[1:],
                                        leaf.dtype)], axis=0)
                        for leaf in leaves]
                obs = self._obs_tree(chunk[0][0], leaves)
                outputs = self._forward(model, obs)
                outputs.pop("hidden", None)
                lo = 0
                for client, seq, n, _leaves, _pin in chunk:
                    part = {k: np.asarray(v[lo:lo + n])
                            for k, v in outputs.items()}
                    lo += n
                    if not client.deliver(seq, epoch, part):
                        # full or too small for the OUTPUT pickle (reply
                        # slots are sized from the obs schema): the worker
                        # will time out, count it, and degrade itself to
                        # local inference — say why, once per client
                        self.reply_drops += 1
                        if not client.drop_warned:
                            client.drop_warned = True
                            print(f"WARNING: inference reply to client "
                                  f"{client.cid} dropped (reply ring full "
                                  f"or slot smaller than the output "
                                  f"frame); that worker will degrade to "
                                  f"local inference")
                self.batches += 1
                self.requests += len(chunk)
                self.rows_served += rows
                with self._lock:
                    self._batch_rows.append(rows)
                    self._queue_wait += waited
                    self._requests_epoch += len(chunk)
                telemetry.span_end("infer.batch", t0, rows=rows,
                                   wait=round(waited, 6), epoch=epoch)

    def _warm_next(self):
        """Compile the forward for one pending client's likely batch
        buckets (min bucket + its lockstep rows_max) with zero
        observations.  Runs on the service thread between batches."""
        import numpy as np

        with self._lock:
            if not self._warm:
                return False
            # peek, don't pop: warm_pending must stay truthful while
            # the compile below blocks this thread (and the beat) —
            # popping first made "warmed" readable a compile-length
            # early, and a request landing in that window died at the
            # client's health deadline (found live, flaky test)
            client = self._clients.get(self._warm[0])
        try:
            if client is not None:
                self._adopt_model()
            # no forward to warm for a sequence net: no worker sends it
            # a row (a net with per-seat state is never wrapped), and
            # its stateless call is the learner's pass over a window
            if client is not None and not getattr(
                    self._model, "is_sequence", False):
                buckets = {_bucket(1, self.cfg.max_batch,
                                   self._bucket_floor),
                           _bucket(client.rows_max, self.cfg.max_batch,
                                   self._bucket_floor)}
                for rows in sorted(buckets):
                    leaves = [np.zeros((rows,) + shape, dtype)
                              for shape, dtype in client.leaf_specs]
                    self._forward(self._model,
                                  self._obs_tree(client, leaves))
        finally:
            with self._lock:
                if self._warm:
                    self._warm.pop(0)
        return client is not None

    def _reap_idle(self):
        """Reclaim clients silent on both rings past CLIENT_IDLE_REAP
        (their worker died or went fully local).  Two-phase: removal
        from the live set now, ring close after GRAVE_GRACE — any
        snapshot iteration taken before removal finishes long before
        the grace expires, so no thread can touch a closing buffer."""
        now = self.clock()
        with self._lock:
            dead = [cid for cid, c in self._clients.items()
                    if now - c.last_seen > self.CLIENT_IDLE_REAP]
            for cid in dead:
                client = self._clients.pop(cid)
                self._grave.append((now + self.GRAVE_GRACE, client))
                self.reaped += 1
                print(f"pipeline: reaped idle client {cid} "
                      f"(silent {self.CLIENT_IDLE_REAP:.0f}s)")
            ready = [c for due, c in self._grave if now >= due]
            self._grave = [(due, c) for due, c in self._grave
                           if now < due]
        for client in ready:
            client.req.close()
            client.rsp.close()
            client.traj.close()
        return bool(dead or ready)

    @property
    def warm_pending(self):
        with self._lock:
            return len(self._warm)

    def _loop(self):
        self.board.beat(epoch=self._epoch)
        while not self._stop:
            if self._kill:
                return  # chaos death: no parting beat, board goes stale
            self._adopt_model()
            worked = self.step()
            if not worked:
                worked = self._warm_next()
            if not worked:
                self._reap_idle()
            self.board.beat(epoch=self._epoch)
            if not worked:
                self.sleep(5e-4)
