"""handyrl_tpu.telemetry — distributed tracing, flight recorder, status.

Public surface (see :mod:`.spans` for the design notes):

  * spans: ``trace_span`` / ``record_span`` / ``add_event`` /
    ``span_begin`` / ``span_end``, configured per process via
    ``configure_from_args`` (the same args dict every child receives);
    live spans are mirrored onto the profiler's clock as ``hrl:<name>``
    where the process handed ``configure`` its annotation class, and
    :mod:`.devtrace` reduces a device trace against them;
  * trace context: ``new_trace`` / ``maybe_trace`` / ``current_trace``
    / ``set_trace`` / ``clear_trace`` and the wire envelope
    ``wrap_trace`` / ``unwrap_trace`` (ridden by
    ``connection.TracedConnection`` and the ``QueueCommunicator``);
  * flight recorder: ``dump`` / ``dump_count`` / ``stall_hook`` /
    ``crash_dump`` / ``install_signal_dump``;
  * exporters: :mod:`.export` (Perfetto ``trace.json``) and
    :mod:`.status` (read-only HTTP snapshot);
  * metrics: ``summarize_lags`` (the per-epoch policy-version-lag
    reduction) and :class:`.histogram.LatencyHistogram` (mergeable
    fixed-bucket log2 latency histogram — the serving tier's p50/p99
    accounting, reusable for any span family);
  * perf attribution: :mod:`.costmodel` (runtime MFU/roofline cost
    accounting over the guarded jit programs — ``CostModel`` /
    ``PerfConfig`` / the one ``DEVICE_PEAKS`` table) and
    :mod:`.attribution` (the per-epoch self-time tree + the
    ``untracked_residual_sec`` wall-time reconciliation), surfaced in
    metrics.jsonl, the status ``perf`` section, and flight-recorder
    dumps via ``register_dump_extra``;
  * the in-flight ledger: :class:`.inflight.InFlight`, by which the
    trainer thread knows how far ahead of the device it runs
    (``device.starved`` spans, ``depth`` / ``done`` on its dispatch
    spans, the seconds ``mfu`` divides by).
"""

from .attribution import (  # noqa: F401
    Attributor,
    self_time_tree,
    untracked_residual,
)
from .costmodel import CostModel, PerfConfig  # noqa: F401
from .histogram import LatencyHistogram  # noqa: F401
from .inflight import InFlight  # noqa: F401
from .spans import (  # noqa: F401
    TRACE_HEAD,
    add_event,
    clear_trace,
    configure,
    configure_from_args,
    crash_dump,
    current_trace,
    dump,
    dump_count,
    enabled,
    flush,
    install_signal_dump,
    maybe_trace,
    mirror,
    new_trace,
    now,
    payload_trace,
    record_span,
    register_dump_extra,
    ring_snapshot,
    set_trace,
    span_begin,
    span_end,
    stall_hook,
    stats,
    summarize_lags,
    trace_span,
    unwrap_trace,
    wrap_trace,
)
