"""Runtime MFU/roofline cost accounting for the guarded jit programs.

XLA's own flops and bytes per guarded program, reduced once per epoch
into metrics.jsonl keys, every run (Podracer, arXiv:2104.06272, treats
this decomposition as the primary dataflow-design signal).  The
seconds it divides by are those in which the device had a step in
flight, from the trainer thread's in-flight ledger (:mod:`.inflight`:
an epoch's wall less its ``device.starved`` spans; with telemetry off,
the dispatch seconds as before): ``mfu`` and ``achieved_tflops`` read
the device's rate while it has work (the benchmark's device-time
roofline, from a trace, is benchmarks/harness/roofline.py).

Three pieces:

  * **The peak table** — ONE per-device-kind (bf16 peak TFLOP/s, peak
    HBM GB/s) table, :data:`DEVICE_PEAKS`.  Unknown kinds (CPU CI
    hosts) resolve to ``(None, None)`` and the roofline verdict reads
    ``unknown`` — unless the run overrides via ``perf.peak_tflops`` /
    ``perf.peak_hbm_gbs`` (:class:`PerfConfig`), which is also how CPU
    e2e tests get real MFU numbers.

  * **The harvest** — :meth:`CostModel.on_compile` plugs into
    ``RetraceGuard.on_compile`` (analysis/guards.py): when a guarded
    program sees a NEW abstract signature, the hook lowers it with the
    live call's arguments (``fn.lower(*args).compile().cost_analysis()``
    — abstract tracing, safe before the donated buffers die) and
    records XLA's own flops/bytes for that program.  The AOT compile is
    NOT shared with the jit's call cache, so a harvest pays one extra
    XLA compile per program per run — unless the persistent
    compilation cache (utils/compile_cache.py) holds it: the harvest
    runs first and writes the entry, the call's own compile then reads
    it back.  Switchable off via ``perf.cost_analysis: false``.  A
    harvest that fails leaves the perf keys None and says why, once
    per program, on stderr.  The harvest also keeps the compiled
    program itself, so that :meth:`CostModel.hlo_text` can print its
    HLO text when asked: the one place a device trace's op events find
    their ``jax.named_scope`` (:mod:`.devtrace`).

  * **The epoch reduction** — :meth:`CostModel.epoch_metrics` turns
    (steps this epoch, seconds with a step in flight) into the
    metrics.jsonl keys ``achieved_tflops`` / ``mfu`` /
    ``arithmetic_intensity`` / ``roofline_verdict``.  The verdict
    compares the program's arithmetic intensity (flops per HBM byte)
    against the device's ridge point (peak_flops / peak_bandwidth):
    below the ridge the program cannot reach peak FLOP/s no matter how
    well it schedules — it is memory-bound, and the fix is batch/fusion
    shape, not overlap.  Keys are ALWAYS present (None when a quantity
    is unknowable) so the metrics schema is stable and the plots'
    ``series()`` skip-absent pattern does the right thing.

jax is imported lazily (device-kind detection only): scripts read the
peak table without dragging a jax runtime in.
"""

import queue
import sys
import threading

# bf16 peak TFLOP/s and peak HBM GB/s per chip by device kind (public
# specs).  Unknown kinds fall back to (None, None) -> mfu None.
DEVICE_PEAKS = {
    "TPU v4": (275.0, 1228.0),
    "TPU v5": (459.0, 2765.0),
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5e": (197.0, 819.0),
    "TPU v6 lite": (918.0, 1640.0),
    "TPU v6e": (918.0, 1640.0),
}


def device_kind():
    """The first device's kind string, or "" when jax is unavailable
    (scripts importing the table never pay for a backend)."""
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return ""


class PerfConfig:
    """Validated view of the ``perf`` config section.

    Keys:
      * ``peak_tflops`` — override the device's bf16 peak TFLOP/s
        (0 = look the device kind up in :data:`DEVICE_PEAKS`).  How
        CPU hosts and unlisted accelerators get real MFU numbers.
      * ``peak_hbm_gbs`` — override peak HBM bandwidth, GB/s (0 =
        table lookup), the roofline verdict's other axis.
      * ``cost_analysis`` — harvest ``compiled.cost_analysis()`` at
        each new guarded-program signature (default on).  The harvest
        is once per program per run; off = flops/bytes unknown and the
        perf keys report None.
    """

    KEYS = ("peak_tflops", "peak_hbm_gbs", "cost_analysis")

    def __init__(self, peak_tflops=0.0, peak_hbm_gbs=0.0,
                 cost_analysis=True):
        self.peak_tflops = float(peak_tflops or 0.0)
        self.peak_hbm_gbs = float(peak_hbm_gbs or 0.0)
        self.cost_analysis = bool(cost_analysis)
        if self.peak_tflops < 0:
            raise ValueError("perf.peak_tflops must be >= 0")
        if self.peak_hbm_gbs < 0:
            raise ValueError("perf.peak_hbm_gbs must be >= 0")

    @classmethod
    def from_config(cls, raw):
        raw = dict(raw or {})
        unknown = set(raw) - set(cls.KEYS)
        if unknown:
            raise ValueError(f"unknown perf keys: {sorted(unknown)}")
        return cls(**raw)


def resolve_peaks(cfg=None, kind=None):
    """(peak_tflops, peak_hbm_gbs) for this run: config overrides win,
    then the :data:`DEVICE_PEAKS` row for ``kind`` (detected when not
    given), else (None, None)."""
    if kind is None:
        kind = device_kind()
    table = DEVICE_PEAKS.get(kind, (None, None))
    tflops = None
    gbs = None
    if cfg is not None and cfg.peak_tflops > 0:
        tflops = cfg.peak_tflops
    elif table[0]:
        tflops = table[0]
    if cfg is not None and cfg.peak_hbm_gbs > 0:
        gbs = cfg.peak_hbm_gbs
    elif table[1]:
        gbs = table[1]
    return tflops, gbs


def _sig(value, digits=4):
    """Round to significant digits, not decimal places: a CPU test
    run's MFU lives at 1e-7 and must not round to a dead 0.0, while a
    TPU run's 0.0897 must not grow noise digits."""
    return float(f"{value:.{digits}g}")


def _normalize_cost(analysis):
    """Fold ``cost_analysis()``'s dict to (flops, bytes)."""
    flops = float(analysis.get("flops", 0.0) or 0.0)
    hbm_bytes = float(analysis.get("bytes accessed", 0.0) or 0.0)
    return flops, hbm_bytes


def _abstractify(args, kwargs):
    """Swap every array leaf for its ShapeDtypeStruct so lowering can
    happen later, off-thread, without holding (possibly donated)
    buffers alive."""
    import jax

    def to_struct(leaf):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf

    return jax.tree.map(to_struct, (args, kwargs))


class CostModel:
    """Per-program flops/bytes registry + the per-epoch MFU/roofline
    reduction.  One per trainer; the inference service's guard shares
    it (its programs land in the same registry under their own
    labels).  Thread contract: ``on_compile`` may fire from the
    trainer thread and the inference batching thread; readers get
    freshly built dicts, never live internals."""

    def __init__(self, cfg=None, kind=None):
        self.cfg = cfg if cfg is not None else PerfConfig()
        self._kind = kind          # lazy: resolved on first use
        self._peaks = None
        self._lock = threading.Lock()
        self._programs = {}        # label -> {flops, bytes, harvests}
        self._compiled = {}        # label -> latest harvested compile
        self.harvest_failures = 0
        self._reported = set()     # labels whose failure was printed
        self._queue = queue.Queue()  # deferred (label, fn, args, kwargs)
        self._worker = None          # lazy daemon drain thread

    @property
    def kind(self):
        if self._kind is None:
            self._kind = device_kind()
        return self._kind

    @property
    def peaks(self):
        if self._peaks is None:
            self._peaks = resolve_peaks(self.cfg, self.kind)
        return self._peaks

    # -- harvest (RetraceGuard.on_compile) --------------------------
    def on_compile(self, label, fn, args, kwargs):
        """Harvest XLA's flops/bytes for one program at a new
        signature.  Runs BEFORE the call executes (the guard's
        contract — lowering needs the donated buffers alive);
        failures count, never raise."""
        if not self.cfg.cost_analysis:
            return
        self._harvest(label, fn, args, kwargs)

    def on_compile_async(self, label, fn, args, kwargs):
        """Non-blocking twin of :meth:`on_compile` for latency-bound
        callers — the inference batching thread, where a blocking AOT
        compile before the first dispatch of a new batch bucket delays
        replies long enough that workers time out and degrade to local
        inference.  The hook snapshots abstract avals NOW (a cheap
        shape walk, safe while the donated buffers are alive) and the
        compile runs on a lazy daemon worker that exits when the queue
        drains.  FIRST signature wins here (unlike the sync hook's
        latest-wins): the serving path re-traces the same program once
        per batch bucket, and re-harvesting each bucket would burn a
        core-second at arbitrary moments — including mid-chaos-respawn,
        when the service can least afford the contention."""
        if not self.cfg.cost_analysis:
            return
        with self._lock:
            if label in self._programs:
                return
        try:
            s_args, s_kwargs = _abstractify(args, kwargs)
        except Exception as exc:
            self._note_failure(label, exc)
            return
        self._queue.put((label, fn, s_args, s_kwargs))
        with self._lock:
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._drain, daemon=True,
                    name="costmodel-harvest")
                self._worker.start()

    def _drain(self):
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                with self._lock:
                    # re-check under the lock: a producer that enqueued
                    # after the Empty above sees the old thread until we
                    # clear the slot, so the queue must be decided here
                    if self._queue.empty():
                        self._worker = None
                        return
                continue
            self._harvest(*item)

    def _harvest(self, label, fn, args, kwargs):
        try:
            lower = getattr(fn, "lower")
            compiled = lower(*args, **kwargs).compile()
            flops, hbm_bytes = _normalize_cost(compiled.cost_analysis())
        except Exception as exc:
            self._note_failure(label, exc)
            return
        with self._lock:
            self._compiled[label] = compiled
            prog = self._programs.setdefault(
                label, {"flops": 0.0, "bytes": 0.0, "harvests": 0})
            # keep the LATEST signature's numbers: a replay-ring
            # growth re-lays the same program at a new geometry and
            # the current geometry is the one the steps now run
            prog["flops"] = flops
            prog["bytes"] = hbm_bytes
            prog["harvests"] += 1

    def _note_failure(self, label, exc):
        """Count a failed harvest and say why ONCE per program: the
        perf keys then read None, and without this line nothing else
        in the run explains it.  A print, never a raise — the caller
        is a step site or the service's drain worker."""
        with self._lock:
            self.harvest_failures += 1
            first = label not in self._reported
            self._reported.add(label)
        if first:
            print(f"WARNING: cost harvest failed for {label!r} "
                  f"({exc!r}); mfu/achieved_tflops for this program "
                  "will read None", file=sys.stderr)

    def program(self, label):
        with self._lock:
            prog = self._programs.get(label)
            return dict(prog) if prog else None

    def hlo_text(self, label):
        """The HLO text of the latest harvested compile of ``label``
        ("" when none, or on a backend that prints none), printed when
        asked: its ``op_name`` metadata names the scope of every
        instruction a device trace shows.  An executable loaded from a
        persistent-cache entry carries the metadata of the build that
        WROTE the entry (JAX keys the cache without it; see
        ``utils.compile_cache.metadata_in_key``)."""
        with self._lock:
            compiled = self._compiled.get(label)
        try:
            return compiled.as_text() if compiled is not None else ""
        except Exception:
            return ""

    # -- epoch reduction ---------------------------------------------
    def epoch_metrics(self, label, device_sec, steps):
        """The metrics.jsonl perf keys for one epoch of ``steps``
        executions of program ``label`` over ``device_sec`` seconds of
        device-step wall time.  Every key is always present; a
        quantity that cannot be known this run is None (JSON null —
        the plot scripts' series() skips it)."""
        prog = self.program(label)
        peak_tflops, peak_gbs = self.peaks
        out = {
            "mfu": None,
            "achieved_tflops": None,
            "arithmetic_intensity": None,
            "roofline_verdict": "unknown",
        }
        if not prog or prog["flops"] <= 0:
            return out
        if prog["bytes"] > 0:
            intensity = prog["flops"] / prog["bytes"]
            out["arithmetic_intensity"] = _sig(intensity)
            if peak_tflops and peak_gbs:
                # ridge point in flops/byte: peak TFLOP/s over peak
                # GB/s is (1e12 flops/s) / (1e9 B/s) = 1e3 flops/B
                ridge = peak_tflops / peak_gbs * 1e3
                out["roofline_verdict"] = (
                    "compute-bound" if intensity >= ridge
                    else "memory-bound")
        if steps > 0 and device_sec > 0:
            achieved = prog["flops"] * steps / device_sec / 1e12
            out["achieved_tflops"] = _sig(achieved)
            if peak_tflops:
                out["mfu"] = _sig(achieved / peak_tflops)
        return out

    # -- status ------------------------------------------------------
    def stats(self):
        """Cumulative snapshot for the status endpoint's ``perf``
        section."""
        peak_tflops, peak_gbs = self.peaks
        with self._lock:
            programs = {label: dict(prog)
                        for label, prog in self._programs.items()}
            failures = self.harvest_failures
        return {
            "device_kind": self.kind,
            "peak_tflops": peak_tflops,
            "peak_hbm_gbs": peak_gbs,
            "cost_analysis": self.cfg.cost_analysis,
            "programs": programs,
            "harvest_failures": failures,
        }
