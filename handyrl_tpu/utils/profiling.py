"""Lightweight profiling: per-section wall timers + XLA trace capture.

The reference has no profiling at all (SURVEY §5); here observability
is first-class:

  * ``SectionTimers`` — near-zero-cost named wall-clock sections for
    the learner hot loop (batch wait vs device step), reported per
    epoch and fed into the metrics jsonl;
  * ``TraceWindow`` — captures a ``jax.profiler`` trace of a span of
    update steps into ``profile_dir`` (viewable in TensorBoard /
    Perfetto), armed by the ``profile_dir`` config key; on stop it
    reduces its own trace (``telemetry.devtrace``) to the step's
    phases and the device's named idle gaps;
  * ``RetraceGuard`` / ``HostTransferGuard`` (re-exported from
    :mod:`handyrl_tpu.analysis.guards`) — compile-count and
    device->host transfer accounting for the hot path, reported per
    epoch in the metrics jsonl (see docs/static_analysis.md).
"""

import json
import os
from collections import defaultdict
from contextlib import contextmanager

import jax

from ..analysis.guards import (  # noqa: F401  (observability surface)
    HostTransferGuard,
    RetraceGuard,
)
from ..telemetry import spans as _telemetry


def profiler_options():
    """The options every trace of this program is taken with: no Python
    tracer (JAX's default slows the trainer thread severalfold, and the
    trace then shows a stall the tracer made) and no HLO proto (with it
    the DRC step's ``while`` loops ran 1.7x slower on the device).  Both
    measured on a v5e (PERF.md, PR 24)."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    return options


class SectionTimers:
    """Accumulate wall time per named section between snapshots.

    Each timed section ALSO records a telemetry span (``trainer.<name>``)
    when telemetry is armed, mirrored onto the profiler's clock like any
    live span, so the trainer's batch_wait/update sections appear on the
    exported Perfetto timeline and on a device trace without a second
    set of instrumentation sites.  One clock, the telemetry clock, is
    read once per edge for both.  ``span=False`` keeps the seconds and
    records no span: for a section whose work records its own
    (``DeviceReplay.ingest`` writes ``trainer.ingest`` only when it
    appended something).  ``attrs`` is a dict the span takes as its
    attrs; it is read when the section closes, so the block may fill
    it in while it runs."""

    def __init__(self, span_prefix="trainer."):
        self.totals = defaultdict(float)
        self.counts = defaultdict(int)
        self.span_prefix = span_prefix

    @contextmanager
    def section(self, name, span=True, attrs=None):
        mirror = _telemetry.mirror(self.span_prefix + name) if span \
            else None
        if mirror is not None:
            mirror.__enter__()
        t0 = _telemetry.now()
        try:
            yield
        finally:
            dur = _telemetry.now() - t0
            if mirror is not None:
                mirror.__exit__(None, None, None)
            self.totals[name] += dur
            self.counts[name] += 1
            if span:
                _telemetry.record_span(self.span_prefix + name, t0, dur,
                                       **(attrs or {}))

    def snapshot(self, reset=True):
        """{name: {"sec": total, "n": count}}, optionally resetting."""
        out = {
            name: {"sec": round(self.totals[name], 4),
                   "n": self.counts[name]}
            for name in self.totals
        }
        if reset:
            self.totals.clear()
            self.counts.clear()
        return out

    def format(self, snap=None):
        snap = self.snapshot() if snap is None else snap
        return " ".join(
            f"{name}:{v['sec']:.2f}s/{v['n']}"
            for name, v in sorted(snap.items())
        )


class TraceWindow:
    """Capture one XLA/TPU profiler trace over a window of steps.

    ``tick()`` once per update step: the trace starts at
    ``start_step`` and stops at ``stop_step`` (after compilation noise
    has settled).  Inactive when ``trace_dir`` is empty.

    ``hlo_text`` is a zero-argument callable that returns the compiled
    step's HLO text (the trainer's cost model keeps it): with it the
    stopped trace is reduced at once — one ``step phases = ...`` line,
    the device's longest idle gaps by the ``hrl:`` span the host was
    in, and ``step_phases.json`` beside the trace.  The reduction never
    fails the run: what it cannot read it says in one line.
    """

    def __init__(self, trace_dir, start_step=10, stop_step=20,
                 hlo_text=None):
        self.trace_dir = trace_dir
        self.start_step = start_step
        self.stop_step = stop_step
        self.hlo_text = hlo_text
        self.step = 0
        self.active = False
        self.done = not trace_dir

    def tick(self):
        if self.done:
            return
        self.step += 1
        if not self.active and self.step >= self.start_step:
            jax.profiler.start_trace(
                self.trace_dir, profiler_options=profiler_options())
            self.active = True
        elif self.active and self.step >= self.stop_step:
            self._stop()
            print(f"profiler trace written to {self.trace_dir}")
            self._reduce()

    def _stop(self):
        jax.profiler.stop_trace()
        self.active = False
        self.done = True

    def _reduce(self):
        from ..telemetry import devtrace

        try:
            hlo = self.hlo_text() if self.hlo_text is not None else ""
            trace = devtrace.load(
                devtrace.find_xplane(self.trace_dir), hlo)
            report = {"step": devtrace.step_phases(trace),
                      "idle": devtrace.idle_gaps(trace)}
        except Exception as exc:
            print(f"profiler trace not reduced ({exc!r})")
            return
        print("step phases = %s" % devtrace.format_phases(report["step"]))
        print("device idle = %s" % devtrace.format_gaps(report["idle"]))
        with open(os.path.join(self.trace_dir, "step_phases.json"),
                  "w") as f:
            json.dump(report, f, indent=1)

    def close(self):
        if self.active:
            self._stop()
