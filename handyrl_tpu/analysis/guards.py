"""Runtime guards: retrace, host-transfer, and resharding accounting.

The static rules in :mod:`.rules`/:mod:`.shardrules` prove what they
can from source; the guards here measure what only a running program
knows:

  * :class:`RetraceGuard` wraps jitted callables and counts retraces —
    the learner's update step must compile exactly once per run per
    mesh shape, and a shape-churn regression (uneven batches, a dtype
    flip) shows up as ``compiles > 1`` long before it shows up as a
    100x slowdown on a TPU profile.  Counting is host-side abstract
    signatures ((treedef, shape, dtype) per call — the part of the jit
    cache key shape churn perturbs), so it works for any callable and
    ignores the committed-ness variants that donated-buffer loops
    create in the real jit cache without recompiling.
  * :class:`HostTransferGuard` counts device->host transfers by
    interposing on the Python-level sync entry points
    (``jax.device_get``, ``np.asarray``, ``np.array``) while armed.
    C-level syncs (``.item()``, ``float()`` on an array) cannot be
    intercepted from Python — the static ``host-sync`` rule covers
    those paths instead.
  * :class:`ShardingContractGuard` wraps jitted callables and counts
    RESHARDING at the call boundary: the first call fixes the
    per-argument sharding contract (per abstract signature), and any
    later call whose leaf arrives laid out differently is an implicit
    reshard — XLA silently copies the array onto the expected layout
    before the program runs, defeating donation and doubling the
    argument's HBM.  The static ``implicit-reshard`` rule catches the
    cases provable from source; this guard catches the rest (shardings
    threaded through config and checkpoints).
  * :class:`StallWatchdog` samples the learner's control-plane loops
    (server loop, communicator reader/writer threads): each loop beats
    once per pass, and a loop silent past ``max_stall_seconds`` is a
    counted ``stall_event`` with its thread's stack dumped once — the
    runtime complement of commlint's ``unbounded-recv``/
    ``reply-mismatch`` rules, catching the wedges the analyzer could
    not prove (or that a suppression claimed were bounded).
  * :class:`NumericsGuard` wraps the update step and latches the
    per-leaf dtype treedef of its arguments at first call: a later
    call whose leaf arrives with a different concrete dtype is a
    counted ``numerics_contract_break`` (the runtime twin of
    numlint's ``dtype-split-brain``/``implicit-upcast`` rules), and a
    weak<->concrete flip is a counted ``weak_upcast`` (the runtime
    twin of ``weak-type-promotion`` — each flip is also a fresh jit
    cache entry).  It also counts nonfinite update steps: the step
    computes a cheap in-graph flag over the loss and grad global
    norm (see ``ops/update.py``), the learner feeds the fetched
    per-step flags to :meth:`NumericsGuard.note_step` at the epoch
    boundary (no extra host syncs), and ``max_nonfinite_steps > 0``
    turns the count into a hard :class:`NumericsError` budget.
  * :class:`LockOrderGuard` wraps the package's lock objects in
    timing/ordering proxies: per-epoch ``lock_contention_sec`` (wall
    time threads spent waiting on guarded locks) and
    ``lock_order_inversions`` (two locks observed acquired in both
    orders at runtime) — the runtime complement of racelint's
    ``lock-order-cycle``/``blocking-under-lock`` rules, catching the
    interleavings the analyzer could not reach (locks passed through
    config, dynamic handler sets).
  * :class:`ResourceLedger` samples the process's resource population
    once per epoch — ``/proc/self/fd`` count (and how many are
    sockets), ``threading.enumerate()`` count, and the shared-memory
    segments visible in ``/dev/shm`` — and reports ``fd_count`` /
    ``thread_count`` / ``shm_segments`` / ``resource_growth`` into
    the metrics jsonl: the runtime complement of leaklint's
    lifecycle rules, catching the leaks the analyzer could not prove
    (handles escaping into containers, C-level fds).  Growth is
    measured against a post-warmup baseline, so a weeks-long serving
    replica that slowly accretes fds is visible as a rising
    ``resource_growth`` curve long before the kernel's fd limit
    kills it; ``max_fd_growth > 0`` turns the budget into a hard
    :class:`ResourceError`.

All are near-zero-cost (an isinstance check / an integer bump per
event) and run armed in production: the learner feeds their per-epoch
deltas into the metrics jsonl, so a regression is visible on the same
plots as the loss curves.
"""

import sys
import threading
import time
import traceback

import jax
import numpy as np


class RetraceError(RuntimeError):
    """A guarded jit compiled more often than its budget allows."""


class HostTransferError(RuntimeError):
    """More device->host transfers than the armed budget allows."""


class ShardingContractError(RuntimeError):
    """More resharding copies at a jit boundary than the budget."""


class NumericsError(RuntimeError):
    """More nonfinite update steps than the armed budget allows."""


class _GuardedJit:
    """Callable proxy that counts retraces of one jitted fn.

    Counts distinct abstract call signatures — (treedef, shape, dtype)
    per leaf — which is exactly the part of the jit cache key that
    shape churn perturbs.  The jit's own ``_cache_size()`` is NOT used:
    it also keys on committed-ness/sharding, so a donated-buffer loop
    (whose second call feeds back the first call's committed outputs)
    legitimately grows that cache without any XLA recompile, and the
    guard must not report it as one.
    """

    # every call is fingerprinted for the first WARM_CALLS, then one
    # in SAMPLE_EVERY: the flatten-and-shape walk over params +
    # optimizer state + batch is ~tens of microseconds, which is real
    # money in a hot loop whose design goal is "the host passes three
    # scalars per step".  Persistent shape churn is still caught
    # within SAMPLE_EVERY steps; a single-call transient between
    # samples can slip through (documented trade).
    WARM_CALLS = 64
    SAMPLE_EVERY = 8

    def __init__(self, guard, fn, label=None):
        self._guard = guard
        self._fn = fn
        self._label = label or guard.name
        self._signatures = set()
        self._calls = 0

    def _signature(self, args, kwargs):
        leaves, treedef = jax.tree.flatten((args, kwargs))
        return treedef, tuple(
            (np.shape(leaf), getattr(leaf, "dtype", type(leaf)))
            for leaf in leaves
        )

    def __call__(self, *args, **kwargs):
        self._calls += 1
        if (self._calls <= self.WARM_CALLS
                or self._calls % self.SAMPLE_EVERY == 0):
            # signature BEFORE the call: donated args are dead after
            sig = self._signature(args, kwargs)
            if sig not in self._signatures:
                self._signatures.add(sig)
                # a NEW signature is (to within the sampling trade
                # above) a fresh compile: the guard's on_compile hook
                # fires here, BEFORE the call executes, because the
                # abstract lowering a cost-analysis harvest needs is
                # only safe while donated argument buffers are alive.
                # Injected rather than imported, like StallWatchdog's
                # on_stall: analysis stays standalone
                hook = self._guard.on_compile
                if hook is not None:
                    try:
                        hook(self._label, self._fn, args, kwargs)
                    except Exception as exc:  # must not kill the step
                        print("WARNING: on_compile hook failed "
                              f"({exc!r})")
        out = self._fn(*args, **kwargs)
        self._guard._after_call()
        return out

    @property
    def compiles(self) -> int:
        return len(self._signatures)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class RetraceGuard:
    """Compile-count accounting over one or more jitted callables.

    ::

        guard = RetraceGuard(max_compiles=1, name="update_step")
        step = guard.wrap(make_update_step(...))
        ...
        guard.compiles        # total compilations so far
        guard.check()         # raises RetraceError over budget

    ``max_compiles=0`` disables the assertion (counting only).  The
    check also runs after every wrapped call, so a retrace surfaces at
    (or within a few steps of — see the sampling note on _GuardedJit)
    the step that caused it, not at the end of the run.

    ``allowance`` widens the budget for compiles the caller knows are
    legitimate — the learner sets it to the replay ring's growth
    count, so a designed T_max re-layout never trips the assertion.
    """

    def __init__(self, max_compiles: int = 0, name: str = "jit"):
        self.max_compiles = int(max_compiles or 0)
        # extra budget for compiles the caller KNOWS are legitimate
        # (e.g. a replay-ring growth re-lays its buffers and the fused
        # step must recompile once): the effective budget is
        # ``max_compiles + allowance``
        self.allowance = 0
        self.name = name
        self.calls = 0
        self._wrapped = []
        # called once per NEWLY seen abstract signature with
        # (label, fn, args, kwargs), BEFORE the call runs — the
        # telemetry cost model hooks its ``compiled.cost_analysis()``
        # harvest here.  Injected rather than imported (the
        # StallWatchdog.on_stall pattern): analysis stays standalone
        self.on_compile = None

    def wrap(self, fn, label=None):
        """Wrap a jitted callable; returns the counting proxy.
        ``label`` names the program for the on_compile hook (defaults
        to the guard's name)."""
        proxy = _GuardedJit(self, fn, label=label)
        self._wrapped.append(proxy)
        return proxy

    @property
    def compiles(self) -> int:
        return sum(proxy.compiles for proxy in self._wrapped)

    def _after_call(self):
        self.calls += 1
        self.check()

    def check(self):
        budget = self.max_compiles + self.allowance
        if self.max_compiles and self.compiles > budget:
            raise RetraceError(
                f"{self.name} compiled {self.compiles} times "
                f"(budget {budget}) over {self.calls} calls "
                f"— input shapes/dtypes are churning; pad batches to "
                f"fixed shapes or mark the varying argument static")


class _ShardedCall:
    """Callable proxy that checks one jitted fn's sharding contract.

    Each argument treedef carries a per-leaf contract that LATCHES on
    the first COMMITTED sharding seen at that leaf; a later committed
    leaf laid out differently is an implicit reshard — XLA copies it
    onto the compiled program's layout before running, and on donated
    arguments the copy defeats the donation.  Two deliberate skips
    keep the count honest:

      * uncommitted values (host numpy, fresh un-placed jnp results —
        ``committed`` is False) have no layout of their own; the jit's
        first placement of them — e.g. the freshly ``optimizer.init``-ed
        state on the learner's first step — is designed
        initialization, not a resharding copy.  On a single device
        everything stays uncommitted and there is nothing to reshard,
        so the guard is inert there by construction;
      * a NEW treedef is a different program (its own compile, its own
        contract), not a reshard of the old one — while a shape-only
        change (the replay ring's T_max growth) keeps the contract,
        and its re-laid buffers legitimately keep their shardings.

    Shardings are read BEFORE the call (donated buffers are dead
    after).  Limitation, documented: an input that arrives on the
    WRONG layout from its very first committed call latches that
    layout and stays quiet here — proving the intended layout from
    source is the static ``implicit-reshard`` rule's job.
    """

    WARM_CALLS = _GuardedJit.WARM_CALLS
    SAMPLE_EVERY = _GuardedJit.SAMPLE_EVERY

    def __init__(self, guard, fn):
        self._guard = guard
        self._fn = fn
        self._contracts = {}
        self._calls = 0
        self.copies = 0

    def _check(self, args, kwargs):
        leaves, treedef = jax.tree.flatten((args, kwargs))
        contract = self._contracts.get(treedef)
        if contract is None or len(contract) != len(leaves):
            contract = self._contracts[treedef] = [None] * len(leaves)
        mismatched = 0
        for i, leaf in enumerate(leaves):
            sharding = getattr(leaf, "sharding", None)
            if sharding is None \
                    or not getattr(leaf, "committed", False):
                continue
            if contract[i] is None:
                contract[i] = sharding
            elif contract[i] != sharding:
                mismatched += 1
        if mismatched:
            self._guard._note(mismatched, self)

    def __call__(self, *args, **kwargs):
        self._calls += 1
        if (self._calls <= self.WARM_CALLS
                or self._calls % self.SAMPLE_EVERY == 0):
            self._check(args, kwargs)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class ShardingContractGuard:
    """Resharding-copy accounting over one or more jitted callables.

    ::

        guard = ShardingContractGuard(name="update_step")
        step = guard.wrap(make_sharded_update_step(...))
        ...
        guard.copies          # resharding copies observed so far
        guard.snapshot()      # copies since the previous snapshot

    The learner arms one around the update step and reports the
    per-epoch delta as ``resharding_copies`` in the metrics jsonl: the
    steady-state value is 0, because params/optimizer state are
    donated back on their own shardings and batches arrive staged onto
    the batch sharding.  Any positive count means an input changed
    layout mid-run — a silent device-to-device copy per step, exactly
    the Podracer failure mode shardlint's ``implicit-reshard`` rule
    catches statically.  ``max_copies > 0`` turns the count into a
    hard assertion (:class:`ShardingContractError`) raised at the
    offending call.  Sampling matches :class:`RetraceGuard`: every
    call during warmup, then one in SAMPLE_EVERY.
    """

    def __init__(self, max_copies: int = 0, name: str = "jit"):
        self.max_copies = int(max_copies or 0)
        self.name = name
        self._last_snapshot = 0
        self._wrapped = []

    def wrap(self, fn):
        """Wrap a jitted callable; returns the checking proxy."""
        proxy = _ShardedCall(self, fn)
        self._wrapped.append(proxy)
        return proxy

    @property
    def copies(self) -> int:
        return sum(proxy.copies for proxy in self._wrapped)

    def _note(self, mismatched: int, proxy: "_ShardedCall"):
        proxy.copies += mismatched
        if self.max_copies and self.copies > self.max_copies:
            raise ShardingContractError(
                f"{self.name}: {self.copies} resharding copies "
                f"(budget {self.max_copies}) — an argument's sharding "
                f"changed mid-run, so XLA inserts a silent copy (and "
                f"defeats donation) on every call; re-stage the input "
                f"on the sharding the jit was built with")

    def snapshot(self) -> int:
        """Copies since the previous snapshot (per-epoch delta)."""
        delta = self.copies - self._last_snapshot
        self._last_snapshot = self.copies
        return delta


class _DtypeCall:
    """Callable proxy that checks one jitted fn's dtype contract.

    Each argument treedef latches a per-leaf ``(dtype, weak_type)``
    signature at first call.  A later call whose leaf arrives with a
    different *concrete* dtype is a contract break — the jit silently
    retraces (or upcasts) and the mixed-precision regime's declared
    boundary is gone.  A weak<->concrete flip (or a weak Python
    scalar changing type) is a weak upcast: cheaper, but each flip is
    its own jit cache entry and its own promotion hazard.  A NEW
    treedef is a different program and gets a fresh contract, exactly
    like :class:`_ShardedCall`; host-side leaves that are neither
    arrays nor Python scalars are skipped.  Signatures are read
    BEFORE the call (donated buffers are dead after) and sampled on
    the :class:`_GuardedJit` schedule.
    """

    WARM_CALLS = _GuardedJit.WARM_CALLS
    SAMPLE_EVERY = _GuardedJit.SAMPLE_EVERY

    def __init__(self, guard, fn):
        self._guard = guard
        self._fn = fn
        self._contracts = {}
        self._calls = 0
        self.contract_breaks = 0
        self.weak_upcasts = 0

    @staticmethod
    def _leaf_sig(leaf):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None:
            return (str(dtype), bool(getattr(leaf, "weak_type", False)))
        if isinstance(leaf, (bool, int, float)):
            return (type(leaf).__name__, True)
        return None  # host-side leaf with no dtype story

    def _check(self, args, kwargs):
        leaves, treedef = jax.tree.flatten((args, kwargs))
        contract = self._contracts.get(treedef)
        if contract is None or len(contract) != len(leaves):
            contract = self._contracts[treedef] = [None] * len(leaves)
        breaks = upcasts = 0
        for i, leaf in enumerate(leaves):
            sig = self._leaf_sig(leaf)
            if sig is None:
                continue
            if contract[i] is None:
                contract[i] = sig
                continue
            if sig == contract[i]:
                continue
            (dtype0, weak0), (dtype1, weak1) = contract[i], sig
            if weak0 or weak1:
                upcasts += 1
            elif dtype0 != dtype1:
                breaks += 1
        if breaks or upcasts:
            self.contract_breaks += breaks
            self.weak_upcasts += upcasts

    def __call__(self, *args, **kwargs):
        self._calls += 1
        if (self._calls <= self.WARM_CALLS
                or self._calls % self.SAMPLE_EVERY == 0):
            self._check(args, kwargs)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


class NumericsGuard:
    """Dtype-contract + nonfinite-step accounting for the update step.

    ::

        guard = NumericsGuard(max_nonfinite=0, name="update_step")
        step = guard.wrap(make_update_step(...))
        ...
        guard.note_step(m["nonfinite"])   # per step, at epoch fetch
        guard.snapshot()                  # per-epoch metric deltas

    Two independent counters ride one guard:

      * **dtype contract** — :meth:`wrap` proxies the jitted step
        through :class:`_DtypeCall`, which latches each argument
        leaf's ``(dtype, weak_type)`` at first call and counts later
        divergence (``numerics_contract_breaks`` for concrete flips,
        ``weak_upcasts`` for weak-type churn).  Steady state is 0/0:
        params and optimizer state are donated back unchanged and
        batches arrive staged on the pipeline's fixed dtypes.
      * **nonfinite steps** — the update step computes a scalar
        in-graph flag (loss or grad-global-norm nonfinite, see
        ``ops/update.py``) that rides the per-step metrics dict; the
        learner feeds the flags to :meth:`note_step` at the epoch
        boundary, after the ONE ``jax.device_get`` it already does —
        zero extra host traffic.  ``max_nonfinite > 0`` raises
        :class:`NumericsError` when the cumulative count exceeds the
        budget (the default 0 counts without asserting, matching the
        other guards).

    ``enabled=False`` makes the guard a true no-op: :meth:`wrap`
    returns its argument unchanged and every counter stays 0.
    """

    def __init__(self, max_nonfinite: int = 0, name: str = "jit",
                 enabled: bool = True):
        self.max_nonfinite = int(max_nonfinite or 0)
        self.name = name
        self.enabled = bool(enabled)
        self.nonfinite_steps = 0
        self._last_nonfinite = 0
        self._last_breaks = 0
        self._last_upcasts = 0
        self._wrapped = []

    def wrap(self, fn):
        """Wrap a jitted callable; returns the checking proxy (or
        ``fn`` itself when the guard is disabled)."""
        if not self.enabled:
            return fn
        proxy = _DtypeCall(self, fn)
        self._wrapped.append(proxy)
        return proxy

    @property
    def contract_breaks(self) -> int:
        return sum(p.contract_breaks for p in self._wrapped)

    @property
    def weak_upcasts(self) -> int:
        return sum(p.weak_upcasts for p in self._wrapped)

    def note_step(self, flag) -> bool:
        """Count one update step's nonfinite flag (0.0 clean, 1.0
        poisoned — at most one count per step by construction).
        Returns whether the step was nonfinite."""
        if not self.enabled:
            return False
        try:
            bad = float(flag) >= 0.5
        except (TypeError, ValueError):
            return False
        if bad:
            self.nonfinite_steps += 1
            if self.max_nonfinite \
                    and self.nonfinite_steps > self.max_nonfinite:
                raise NumericsError(
                    f"{self.name}: {self.nonfinite_steps} nonfinite "
                    f"update steps (budget {self.max_nonfinite}) — "
                    f"the loss or gradient went NaN/Inf; check the "
                    f"nonfinite-risk lint findings and the lr/clip "
                    f"settings before the parameters are unrecoverable")
        return bad

    def snapshot(self) -> dict:
        """Per-epoch deltas since the previous snapshot, keyed exactly
        as the metrics jsonl expects."""
        breaks, upcasts = self.contract_breaks, self.weak_upcasts
        out = {
            "nonfinite_steps": self.nonfinite_steps
            - self._last_nonfinite,
            "numerics_contract_breaks": breaks - self._last_breaks,
            "weak_upcasts": upcasts - self._last_upcasts,
        }
        self._last_nonfinite = self.nonfinite_steps
        self._last_breaks = breaks
        self._last_upcasts = upcasts
        return out

    def stats(self) -> dict:
        """Cumulative totals for the status endpoint."""
        return {"nonfinite_steps": self.nonfinite_steps,
                "numerics_contract_breaks": self.contract_breaks,
                "weak_upcasts": self.weak_upcasts,
                "max_nonfinite_steps": self.max_nonfinite}


class StallWatchdog:
    """Samples registered control-plane loops for silent wedges.

    ::

        dog = StallWatchdog(max_stall_seconds=60.0)
        dog.start()
        while serving:
            dog.beat("server")     # once per loop pass
            ...
        dog.stop()

    Each watched loop calls :meth:`beat` once per pass (a dict store —
    nanoseconds, safe from any thread).  A background sampler checks
    every ``max_stall_seconds / 4``: a loop whose last beat is older
    than the threshold transitions to STALLED — one counted
    ``stall_event``, plus a one-shot stack dump of the silent thread
    (via ``sys._current_frames``) so the log says *where* it is
    blocked, not just that it is.  A loop that beats again recovers
    and can stall again later (each episode counts once).

    The learner arms one over its server loop and the communicator's
    reader/writer threads and reports the per-epoch ``stall_events``
    delta in the metrics jsonl next to ``retrace_count`` /
    ``resharding_copies`` / the heartbeat stats; the steady-state
    value is 0 because every control-plane wait in the package is
    bounded (a timeout, a sweep, or a supervised peer — the commlint
    ``unbounded-recv`` contract).  Any positive count means a wedge
    the static analysis could not see: a blocked round trip whose
    suppression reason turned out to be wrong, a handler that stopped
    replying, a lock held across an epoch.

    The clock is injectable so expiry tests are exact; with an
    injected clock the sampler thread is usually left unstarted and
    :meth:`sample` driven manually.
    """

    def __init__(self, max_stall_seconds: float = 60.0,
                 clock=time.monotonic):
        self.max_stall = float(max_stall_seconds or 60.0)
        self.clock = clock
        self.stall_events = 0
        self._last_snapshot = 0
        self._loops = {}  # name -> [last_beat, stalled, thread_ident]
        self._lock = threading.Lock()
        self._thread = None
        self._stop = threading.Event()
        # called once per NEWLY stalled loop with (name, silent_sec):
        # the learner wires the telemetry flight-recorder dump here, so
        # a stall leaves its causal timeline behind, not just a stack.
        # Injected rather than imported: analysis stays standalone
        self.on_stall = None

    # -- liveness intake --------------------------------------------
    def beat(self, loop: str = "server"):
        """Prove one loop alive (call once per loop pass)."""
        now = self.clock()
        with self._lock:
            state = self._loops.get(loop)
            if state is None:
                self._loops[loop] = [now, False,
                                     threading.get_ident()]
            else:
                state[0] = now
                state[1] = False  # a beating loop has recovered
                state[2] = threading.get_ident()

    # -- sampling ----------------------------------------------------
    def sample(self, now=None) -> int:
        """One watchdog pass: returns how many loops NEWLY stalled."""
        if now is None:
            now = self.clock()
        newly = []
        with self._lock:
            for name, state in self._loops.items():
                if state[1] or now - state[0] <= self.max_stall:
                    continue
                state[1] = True
                self.stall_events += 1
                newly.append((name, now - state[0], state[2]))
        hook = self.on_stall
        for name, silent, ident in newly:
            self._dump(name, silent, ident)
            if hook is not None:
                try:
                    hook(name, silent)
                except Exception as exc:  # a dead hook must not kill
                    print(f"WARNING: on_stall hook failed ({exc!r})")
        return len(newly)

    def _dump(self, name, silent, ident):
        frame = sys._current_frames().get(ident)
        where = "".join(traceback.format_stack(frame)) if frame \
            else "  <thread gone>\n"
        print(f"WARNING: control-plane loop '{name}' silent for "
              f"{silent:.1f}s (> max_stall_seconds={self.max_stall}); "
              f"stack of the stalled thread:\n{where}", end="")

    def snapshot(self) -> int:
        """Stall events since the previous snapshot (per-epoch delta)."""
        with self._lock:
            delta = self.stall_events - self._last_snapshot
            self._last_snapshot = self.stall_events
            return delta

    # -- sampler thread ----------------------------------------------
    def start(self):
        if self._thread is not None:
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _run(self):
        interval = max(0.5, self.max_stall / 4.0)
        while not self._stop.wait(interval):
            self.sample()

    def stop(self):
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=5)


class HostTransferGuard:
    """Context manager counting device->host transfers while armed.

    ::

        with HostTransferGuard() as guard:
            run_epoch()
        print(guard.transfers)

    Counts one transfer per ``jax.device_get`` call that touches a jax
    array and one per ``np.asarray``/``np.array`` call on a jax array.
    A long-lived guard can stay armed across epochs and report deltas
    via :meth:`snapshot`.  Not reentrant (it patches module-level
    entry points); arm one per process.
    """

    def __init__(self, max_transfers: int = 0):
        self.max_transfers = int(max_transfers or 0)
        self.transfers = 0
        self._last_snapshot = 0
        self._lock = threading.Lock()
        self._saved = None

    # -- counting ----------------------------------------------------
    @staticmethod
    def _contains_jax_array(value, budget: int = 64, depth: int = 3):
        """Bounded containment probe: visits at most ``budget`` nodes
        ``depth`` levels deep.  The guard is armed process-wide, so
        this must NOT walk arbitrary host data — ``np.array(big_list)``
        with a million floats costs a handful of isinstance checks
        here, not a full tree flatten.  Deeply-buried device arrays
        past the bound go uncounted (documented heuristic)."""
        if isinstance(value, jax.Array):
            return True
        if depth == 0 or budget <= 0:
            return False
        if isinstance(value, dict):
            items = value.values()
        elif isinstance(value, (list, tuple)):
            items = value
        else:
            return False
        for i, item in enumerate(items):
            if i >= budget:
                return False
            if HostTransferGuard._contains_jax_array(
                    item, budget // 4, depth - 1):
                return True
        return False

    def _note(self, value) -> None:
        if isinstance(value, np.ndarray):
            return  # fast path: host arrays dominate np.asarray traffic
        if not self._contains_jax_array(value):
            return
        with self._lock:
            self.transfers += 1
            if self.max_transfers and self.transfers > self.max_transfers:
                raise HostTransferError(
                    f"host-transfer budget exceeded: {self.transfers} "
                    f"device->host transfers (budget "
                    f"{self.max_transfers})")

    def snapshot(self) -> int:
        """Transfers since the previous snapshot (per-epoch delta)."""
        with self._lock:
            delta = self.transfers - self._last_snapshot
            self._last_snapshot = self.transfers
            return delta

    # -- arming ------------------------------------------------------
    def __enter__(self):
        if self._saved is not None:
            raise RuntimeError("HostTransferGuard is not reentrant")
        saved = {
            "device_get": jax.device_get,
            "asarray": np.asarray,
            "array": np.array,
        }

        # fully generic signatures: the originals accept their first
        # argument by keyword too (np.array(object=...), np.asarray(a=...),
        # jax.device_get(x=...)), and a wrapper that renames it would
        # crash any caller using the documented keyword form
        def device_get(*args, **kwargs):
            self._note(args[0] if args else kwargs.get("x"))
            return saved["device_get"](*args, **kwargs)

        def asarray(*args, **kwargs):
            self._note(args[0] if args else kwargs.get("a"))
            return saved["asarray"](*args, **kwargs)

        def array(*args, **kwargs):
            self._note(args[0] if args else kwargs.get("object"))
            return saved["array"](*args, **kwargs)

        jax.device_get = device_get
        np.asarray = asarray
        np.array = array
        self._saved = saved
        return self

    def __exit__(self, exc_type, exc, tb):
        saved, self._saved = self._saved, None
        if saved is not None:
            jax.device_get = saved["device_get"]
            np.asarray = saved["asarray"]
            np.array = saved["array"]
        return False


class _GuardedLock:
    """Proxy around one lock that reports waits and ordering to its
    :class:`LockOrderGuard`.  Drop-in for ``threading.Lock`` /
    ``RLock``: ``with``, ``acquire``/``release``, and anything else
    forwards to the wrapped lock."""

    def __init__(self, guard: "LockOrderGuard", inner, name: str):
        self._guard = guard
        self._inner = inner
        self._name = name

    def acquire(self, blocking=True, timeout=-1):
        clock = self._guard.clock
        t0 = clock()
        got = self._inner.acquire(blocking, timeout)
        waited = max(0.0, clock() - t0)
        if got:
            self._guard._note_acquired(self._name, waited)
        elif waited:
            self._guard._note_wait(waited)
        return got

    def release(self):
        self._inner.release()
        self._guard._note_released(self._name)

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    def __getattr__(self, attr):
        return getattr(self._inner, attr)


class LockOrderGuard:
    """Runtime lock-order/contention accounting for the control plane.

    Racelint's ``lock-order-cycle`` proves what it can from source;
    this guard watches the locks that actually run.  :meth:`wrap`
    replaces a lock with a :class:`_GuardedLock` proxy (and
    :meth:`arm` does so in place on an object attribute); every
    acquire then

      * accumulates the wall time the acquiring thread waited
        (``lock_contention_sec`` — uncontended acquires cost
        microseconds and contribute ~0);
      * records the per-thread held-set and, for each (held, new)
        pair, the first-seen acquisition direction; observing the
        *reverse* direction later is a counted
        ``lock_order_inversion`` — a latent ABBA deadlock that simply
        has not fired yet.

    Reentrant re-acquire of a lock already held by the thread records
    no pair (RLocks do that by design).  ``clock`` is injectable for
    tests.  :meth:`snapshot` returns per-epoch deltas for the metrics
    jsonl; :meth:`stats` the cumulative totals for the status
    endpoint.  Near-zero cost: two clock reads and a couple of dict
    ops per acquire, on locks that guard microsecond critical
    sections.
    """

    def __init__(self, clock=time.monotonic):
        self.clock = clock
        self.contention_sec = 0.0
        self.inversions = 0
        self._last_contention = 0.0
        self._last_inversions = 0
        self._names = []                  # wrap() order, for stats()
        self._pairs = {}                  # frozenset({a,b}) -> (a, b)
        self._meta = threading.Lock()     # guards the counters above
        self._held = threading.local()    # per-thread stack of names

    # -- wrapping -----------------------------------------------------
    def wrap(self, lock, name: str):
        """Wrap ``lock`` in a reporting proxy registered as ``name``."""
        if isinstance(lock, _GuardedLock):
            return lock
        with self._meta:
            if name not in self._names:
                self._names.append(name)
        return _GuardedLock(self, lock, name)

    def arm(self, obj, attr: str = "_lock", name=None) -> bool:
        """Replace ``obj.attr`` with its wrapped proxy in place.
        Returns False (and does nothing) when the object is None, the
        attribute is missing, or it is already wrapped — so the
        learner can arm every subsystem it *might* have without
        caring which are enabled this run."""
        if obj is None or not hasattr(obj, attr):
            return False
        lock = getattr(obj, attr)
        if lock is None or isinstance(lock, _GuardedLock):
            return False
        if name is None:
            name = f"{type(obj).__name__}.{attr}"
        setattr(obj, attr, self.wrap(lock, name))
        return True

    # -- proxy callbacks ----------------------------------------------
    def _stack(self):
        stack = getattr(self._held, "stack", None)
        if stack is None:
            stack = self._held.stack = []
        return stack

    def _note_acquired(self, name: str, waited: float):
        stack = self._stack()
        reentrant = name in stack
        if not reentrant and stack:
            with self._meta:
                self.contention_sec += waited
                for held in stack:
                    pair = frozenset((held, name))
                    first = self._pairs.get(pair)
                    if first is None:
                        self._pairs[pair] = (held, name)
                    elif first != (held, name):
                        self.inversions += 1
        elif waited:
            self._note_wait(waited)
        stack.append(name)

    def _note_released(self, name: str):
        stack = self._stack()
        # pop the most recent occurrence: releases may be unnested
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] == name:
                del stack[i]
                break

    def _note_wait(self, waited: float):
        with self._meta:
            self.contention_sec += waited

    # -- reporting ----------------------------------------------------
    def snapshot(self) -> dict:
        """Per-epoch deltas since the previous snapshot, keyed exactly
        as the metrics jsonl expects."""
        with self._meta:
            contention = self.contention_sec - self._last_contention
            inversions = self.inversions - self._last_inversions
            self._last_contention = self.contention_sec
            self._last_inversions = self.inversions
        return {"lock_contention_sec": round(contention, 6),
                "lock_order_inversions": inversions}

    def stats(self) -> dict:
        """Cumulative totals for the status endpoint."""
        with self._meta:
            return {"locks_guarded": len(self._names),
                    "lock_contention_sec": round(self.contention_sec, 6),
                    "lock_order_inversions": self.inversions}


class ResourceError(RuntimeError):
    pass


class ResourceLedger:
    """Per-epoch resource-population sampling (the leak soak meter).

    leaklint proves from source that every acquisition has an owner
    who releases it; this ledger measures the population that actually
    runs — because handles escape into containers, C extensions open
    fds Python never sees, and a suppression's "process-lifetime"
    claim can simply be wrong.  Each :meth:`snapshot` (the learner
    calls it once per epoch, next to the other guards) samples:

      * ``fd_count`` — entries in ``/proc/self/fd``;
      * ``thread_count`` — ``len(threading.enumerate())``;
      * ``shm_segments`` — ``psm_*`` segments in ``/dev/shm`` (the
        default names ``multiprocessing.shared_memory`` gives the
        rings and boards);
      * ``resource_growth`` — fds above the post-warmup baseline.

    The first ``warmup_epochs`` snapshots are bring-up (workers
    dialing in, rings mapping) and set the baseline at the end of the
    window; after that, growth is measured against the baseline so a
    slow accretion shows up as a rising ``resource_growth`` curve on
    the same plots as the loss.  ``max_fd_growth > 0`` makes the
    budget hard: a post-warmup snapshot whose growth exceeds it
    raises :class:`ResourceError` (default 0 = count and report,
    never raise — sampling must not be able to kill a healthy run).

    Sampling is three directory listings per EPOCH — noise next to a
    single update step.  On hosts without ``/proc`` the fd/socket
    samples degrade to 0 and the ledger still reports (the keys stay
    present so the metrics schema is stable).  The proc/shm paths are
    injectable so leak tests can point the ledger at a fixture tree.
    """

    def __init__(self, max_fd_growth: int = 0, warmup_epochs: int = 2,
                 proc_fd_dir: str = "/proc/self/fd",
                 shm_dir: str = "/dev/shm"):
        self.max_fd_growth = max(0, int(max_fd_growth or 0))
        self.warmup_epochs = max(0, int(warmup_epochs))
        self.proc_fd_dir = proc_fd_dir
        self.shm_dir = shm_dir
        self.epochs = 0
        self.baseline = None          # (fd, threads) post-warmup
        self.peak_growth = 0
        self.last = None              # most recent sample dict
        self._lock = threading.Lock()

    # -- sampling ----------------------------------------------------
    def sample(self) -> dict:
        """One population sample (no epoch bookkeeping)."""
        import os

        try:
            fds = os.listdir(self.proc_fd_dir)
        except OSError:
            fds = []
        sockets = 0
        for fd in fds:
            try:
                target = os.readlink(
                    os.path.join(self.proc_fd_dir, fd))
            except OSError:
                continue
            if target.startswith("socket:"):
                sockets += 1
        try:
            shm = sum(1 for name in os.listdir(self.shm_dir)
                      if name.startswith("psm_"))
        except OSError:
            shm = 0
        return {"fd_count": len(fds),
                "thread_count": len(threading.enumerate()),
                "shm_segments": shm,
                "socket_count": sockets}

    def snapshot(self) -> dict:
        """One epoch tick: sample, update the baseline/growth
        bookkeeping, and return the metrics-jsonl keys.  Raises
        :class:`ResourceError` only when ``max_fd_growth > 0`` and a
        post-warmup sample exceeds the budget."""
        sampled = self.sample()
        with self._lock:
            self.epochs += 1
            self.last = sampled
            if self.baseline is None \
                    and self.epochs > self.warmup_epochs:
                self.baseline = (sampled["fd_count"],
                                 sampled["thread_count"])
            growth = 0
            if self.baseline is not None:
                growth = max(0, sampled["fd_count"] - self.baseline[0])
                self.peak_growth = max(self.peak_growth, growth)
            budget = self.max_fd_growth
        record = {"fd_count": sampled["fd_count"],
                  "thread_count": sampled["thread_count"],
                  "shm_segments": sampled["shm_segments"],
                  "resource_growth": growth}
        if budget and growth > budget:
            raise ResourceError(
                f"fd count grew by {growth} over the post-warmup "
                f"baseline (> max_fd_growth={budget}): "
                f"{sampled['fd_count']} fds "
                f"({sampled['socket_count']} sockets), "
                f"{sampled['shm_segments']} shm segments — a resource "
                f"leak leaklint could not see; check the suppressions "
                f"and container-held handles")
        return record

    # -- reporting ----------------------------------------------------
    def stats(self) -> dict:
        """Cumulative totals for the status endpoint."""
        with self._lock:
            last = dict(self.last) if self.last else {}
            return {"fd_count": last.get("fd_count", 0),
                    "thread_count": last.get("thread_count", 0),
                    "shm_segments": last.get("shm_segments", 0),
                    "socket_count": last.get("socket_count", 0),
                    "baseline_fd": None if self.baseline is None
                    else self.baseline[0],
                    "peak_fd_growth": self.peak_growth,
                    "max_fd_growth": self.max_fd_growth,
                    "epochs_sampled": self.epochs}

    def delta_line(self, since: dict) -> str:
        """One-line human delta vs an earlier :meth:`sample`: what
        moved, for a log line between two stretches of work."""
        now = self.sample()

        def arrow(key):
            a, b = since.get(key, 0), now.get(key, 0)
            sign = f"{b - a:+d}" if b != a else "±0"
            return f"{a}->{b} ({sign})"

        return (f"resources: fd {arrow('fd_count')}, "
                f"threads {arrow('thread_count')}, "
                f"shm {arrow('shm_segments')}")
