#!/usr/bin/env python
"""Hold the trainer thread's in-flight ledger to a device trace.

    python scripts/inflight_check.py <trace dir or .xplane.pb> <run dir> \
        [--gap-ms 10]

The ledger (``handyrl_tpu/telemetry/inflight.py``) says from INSIDE the
program when the device had no step: ``device.starved`` spans in the
run directory's ``spans-<pid>.jsonl``, each a lower bound, each with
``since_ms`` to widen it into the upper one.  A profiler trace says the
same from outside: the time between two ``XLA Modules`` events of the
step program on chip 0.  This script puts both on one clock and prints,
for the traced stretch (first step's start to last step's end):

  * the trace's idle time between steps (and how much of it lies in
    gaps under a tenth of a millisecond: the device's own latency from
    one queued step to the next, which is no starvation), and the
    ledger's reading (``spans`` = the ``device.starved`` spans clipped
    to the stretch) inside its bracket (``upper`` = the spans widened
    by ``since_ms``: the device went idle somewhere before the poll
    that found it so; ``lower`` = the spans less their part inside
    ``trainer.update``: the device began its step somewhere in the
    dispatch that closed the stretch), as seconds and as points of the
    stretch;
  * every gap of the trace longer than ``--gap-ms``: whether a
    ``device.starved`` span overlaps it, the span ``devtrace`` names it
    by (the innermost ``hrl:`` span over most of the gap), and the span
    that the same split of the LEDGER's stretch over the span log's own
    records gives most of it to.

The one clock: ``device.starved`` is recorded after the fact and has no
mirror in the trace; ``trainer.update`` has both a record (telemetry
clock) and an ``hrl:trainer.update`` event (profiler clock), in the
same order.  The offset between the two is the median over the matched
pairs.  Exit code 0 whatever it finds: it is a report, not a gate.
"""

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

UPDATE = "trainer.update"
STARVED = "device.starved"
MATCH = 200     # dispatches a candidate alignment is held to
BACK_TO_BACK = 1e-4   # a gap under this is one queued step's hand-over


def clock_offset(trace_updates, log_updates):
    """Seconds to ADD to a profiler-clock time (s) to get the telemetry
    clock's: ``trace_updates`` are the starts (s) of the trace's
    ``hrl:trainer.update`` events, ``log_updates`` the ``ts`` of the
    log's ``trainer.update`` records, both sorted; the trace's are a
    run of the log's.  The run is found by its rhythm: the alignment
    under which the first ``MATCH`` starts disagree least."""
    if not trace_updates or len(log_updates) < len(trace_updates):
        raise ValueError("the trace holds no run of the log's dispatches")
    head = trace_updates[:MATCH]
    best = None
    for j in range(len(log_updates) - len(trace_updates) + 1):
        off = log_updates[j] - head[0]
        err = max(abs(log_updates[j + i] - (s + off))
                  for i, s in enumerate(head))
        if best is None or err < best[0]:
            best = (err, j)
    err, j = best
    if err > 2e-3:
        raise ValueError(f"no alignment better than {err * 1e3:.2f} ms")
    return statistics.median(
        log_updates[j + i] - s for i, s in enumerate(trace_updates))


def step_gaps(trace):
    """``(lo, hi, steps, gaps)`` on the profiler's clock (s): the
    stretch from the first step's start to the last step's end on chip
    0, the step count, and the idle intervals between two steps."""
    from handyrl_tpu.telemetry import devtrace

    plane = devtrace._chip0(trace)
    module = trace.get("module") or "jit_step"
    steps = sorted((s * 1e-9, (s + d) * 1e-9)
                   for n, s, d in devtrace._line(plane, devtrace.MODULES_LINE)
                   if n.split("(")[0] == module)
    if len(steps) < 2:
        raise ValueError(f"the trace holds under two {module} events")
    gaps, edge = [], steps[0][1]
    for a, b in steps[1:]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return steps[0][0], edge, len(steps), gaps


def _clipped(a, b, lo, hi):
    return max(0.0, min(b, hi) - max(a, lo))


def compare(trace, records, gap_ms=10.0):
    """The report as a dict (see the module's docstring); ``trace`` is
    ``devtrace.load``'s plain data, ``records`` the learner's span
    records."""
    from handyrl_tpu.telemetry import devtrace

    host = devtrace._host_spans(trace)
    trace_updates = sorted(s * 1e-9 for n, s, _, rank in host
                           if n == UPDATE and rank == 0)
    updates = sorted((r for r in records if r["name"] == UPDATE),
                     key=lambda r: r["ts"])
    tids = [r["tid"] for r in updates]
    trainer = max(set(tids), key=tids.count)
    off = clock_offset(trace_updates,
                       [r["ts"] for r in updates if r["tid"] == trainer])
    lo, hi, steps, gaps = step_gaps(trace)
    # the ledger's stretches on the profiler's clock
    starved = [(r["ts"] - off, r["ts"] + r["dur"] - off,
                1e-3 * float(r["attrs"].get("since_ms", 0.0)),
                r["attrs"].get("at"))
               for r in records if r["name"] == STARVED]
    spans = sum(_clipped(a, b, lo, hi) for a, b, _, _ in starved)
    upper = sum(_clipped(a - since, b, lo, hi)
                for a, b, since, _ in starved)
    dispatches = [(r["ts"] - off, r["ts"] + r["dur"] - off) for r in updates
                  if r["tid"] == trainer]
    lower = spans - sum(_clipped(max(a, s), min(b, e), lo, hi)
                        for a, b, _, _ in starved
                        for s, e in dispatches if e > a and s < b)
    idle = sum(b - a for a, b in gaps)
    handover = sum(b - a for a, b in gaps if b - a < BACK_TO_BACK)
    # the span log's own records of the trainer thread, as devtrace's
    # split wants them: (name, start, end, rank) in ns
    own = [(r["name"], (r["ts"] - off) * 1e9,
            (r["ts"] + r["dur"] - off) * 1e9, 0)
           for r in records
           if r["tid"] == trainer and r["name"] != STARVED and r["dur"] > 0]
    long_gaps = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1]):
        if b - a < 1e-3 * gap_ms:
            break
        over = [(s, e, at) for s, e, _, at in starved if e > a and s < b]
        named = devtrace._innermost(host, a * 1e9, b * 1e9)
        entry = {"at_s": round(a - lo, 4), "gap_ms": round(1e3 * (b - a), 3),
                 "starved_ms": round(1e3 * sum(
                     _clipped(s, e, a, b) for s, e, _ in over), 3),
                 "trace_names": max(named, key=named.get),
                 "ledger_names": None,
                 "found_at": [at for _, _, at in over]}
        if over:
            split = {}
            for s, e, _ in over:
                for name, ns in devtrace._innermost(
                        own, s * 1e9, e * 1e9).items():
                    split[name] = split.get(name, 0.0) + ns
            entry["ledger_names"] = max(split, key=split.get)
        long_gaps.append(entry)
    stretch = hi - lo
    return {
        "stretch_s": round(stretch, 4), "steps": steps,
        "clock_offset_s": round(off, 6),
        "trace_idle_s": round(idle, 6),
        "trace_idle_back_to_back_s": round(handover, 6),
        "ledger_spans_s": round(spans, 6),
        "ledger_lower_s": round(lower, 6), "ledger_upper_s": round(upper, 6),
        "trace_idle_share": round(100.0 * idle / stretch, 4),
        "ledger_spans_share": round(100.0 * spans / stretch, 4),
        "ledger_lower_share": round(100.0 * lower / stretch, 4),
        "ledger_upper_share": round(100.0 * upper / stretch, 4),
        "bracket_points": round(100.0 * (upper - lower) / stretch, 4),
        "inside_bracket": lower <= idle - handover <= upper,
        "starved_spans": sum(1 for a, b, _, _ in starved
                             if b > lo and a < hi),
        "long_gaps": len(long_gaps),
        "long_gaps_covered": sum(1 for g in long_gaps if g["found_at"]),
        "long_gaps_named_alike": sum(
            1 for g in long_gaps if g["ledger_names"] == g["trace_names"]),
        "gaps": long_gaps,
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("trace")
    parser.add_argument("run_dir")
    parser.add_argument("--gap-ms", type=float, default=10.0)
    opts = parser.parse_args(argv)

    from handyrl_tpu.telemetry import devtrace
    from handyrl_tpu.telemetry.export import collect_run

    path = opts.trace if opts.trace.endswith(".pb") \
        else devtrace.find_xplane(opts.trace)
    _roles, records = collect_run(opts.run_dir)
    updates = [r for r in records if r["name"] == UPDATE]
    if not updates:
        raise SystemExit(f"no {UPDATE} record under {opts.run_dir}")
    pids = [r["pid"] for r in updates]
    pid = max(set(pids), key=pids.count)       # the learner's process
    report = compare(devtrace.load(path),
                     [r for r in records if r["pid"] == pid], opts.gap_ms)
    gaps = report.pop("gaps")
    print(json.dumps(report))
    for gap in gaps[:40]:
        print(json.dumps(gap))
    return 0


if __name__ == "__main__":
    sys.exit(main())
