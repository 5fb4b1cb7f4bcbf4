"""Per-layer metrics read from what the PROGRAM recorded about itself:
its own spans (``handyrl_tpu/telemetry/spans.py``, flushed to
``spans-<pid>.jsonl`` in the run directory) and the fused step's phases
(``Trainer.step_profile``).  The outside-in twins in ``layers.py`` read
the wrappers of ``probes.py``; these read the inside.

The program's spans are on the telemetry clock (``time.monotonic``),
the harness on ``time.perf_counter``: one offset, measured when the log
is read, maps the first onto the second; spans are then clipped to the
window ``[run.t_open, run.t_close)``.  No reader raises: with the span
log missing, telemetry off, a program that records no such span (the
parent of the PR that added them) or no TPU, it returns None and the
run still prints its line.
"""

import functools
import os
import time

from .layers import percentile

TRAINER_THREAD = ("trainer.ingest", "trainer.boundary", "trainer.handoff",
                  "trainer.update")
_EPS = 2e-6       # records are rounded to 1e-6 s


class Log:
    """The learner process's span records on the harness's clock."""

    def __init__(self, records, offset, lo, hi):
        self.lo, self.hi = lo, hi
        self.by_name = {}
        for rec in records:
            start = float(rec["ts"]) + offset
            self.by_name.setdefault(rec["name"], []).append(
                (start, start + float(rec["dur"]), rec.get("tid"),
                 rec.get("attrs") or {}))

    def spans(self, name, tid=None):
        return [s for s in self.by_name.get(name, ())
                if tid is None or s[2] == tid]

    def clipped_s(self, name, tid=None):
        """Seconds of the window inside spans of this name."""
        return sum(max(0.0, min(b, self.hi) - max(a, self.lo))
                   for a, b, _, _ in self.spans(name, tid))

    def closed(self, name, tid=None):
        """Spans of this name that ended inside the window."""
        return [s for s in self.spans(name, tid)
                if self.lo <= s[1] < self.hi]

    def trainer_tid(self):
        """The thread that dispatches the steps."""
        tids = [s[2] for s in self.by_name.get("trainer.update", ())]
        return max(set(tids), key=tids.count) if tids else None


def read_log(run_dir, offset, lo, hi, pid=None):
    from handyrl_tpu.telemetry.export import collect_run

    _roles, records = collect_run(run_dir)
    if pid is not None:
        records = [r for r in records if r.get("pid") == pid]
    return Log(records, offset, lo, hi) if records else None


def load(run):
    """The run's ``Log`` (read once), or None when there is none."""
    if not hasattr(run, "_program_spans"):
        run._program_spans = None
        from handyrl_tpu import telemetry

        if telemetry.enabled():
            telemetry.flush()
            offset = time.perf_counter() - telemetry.now()
            metrics = str(run.probes.learner.args.get("metrics_path") or "")
            if metrics:
                run._program_spans = read_log(
                    os.path.dirname(os.path.abspath(metrics)), offset,
                    run.t_open, run.t_close, pid=os.getpid())
    return run._program_spans


def _reader(fn):
    """A reader finds its number or returns None; it never raises."""
    @functools.wraps(fn)
    def read(run):
        try:
            return fn(run)
        except Exception as exc:
            run.notes[fn.__name__ + "_unread"] = repr(exc)
            return None
    return read


# -- ring ingest ------------------------------------------------------
def _ingest_part(part):
    def read(run):
        log = load(run)
        tid = log.trainer_tid()
        episodes = sum(int(s[3].get("episodes", 0))
                       for s in log.closed("trainer.ingest", tid))
        if not episodes or not log.spans(part, tid):
            return None
        return 1e3 * log.clipped_s(part, tid) / episodes
    read.__name__ = part.replace(".", "_") + "_ms_per_episode"
    return _reader(read)


ingest_decompress_ms_per_episode = _ingest_part("ingest.decompress")
ingest_pad_ms_per_episode = _ingest_part("ingest.pad")
ingest_append_ms_per_episode = _ingest_part("ingest.append")


@_reader
def ring_queue_wait_p95_ms(run):
    waits = [w for s in load(run).closed("ingest.append")
             for w in s[3].get("wait_ms", ())]
    return percentile(waits, 95) if waits else None


# -- conductor --------------------------------------------------------
def _boundary_part(part):
    def read(run):
        log = load(run)
        boundaries = log.closed("trainer.boundary")
        if not boundaries or not log.spans(part):
            return None
        inside = sum(b - a for a, b, _, _ in log.spans(part)
                     if any(lo - _EPS <= a and b <= hi + _EPS
                            for lo, hi, _, _ in boundaries))
        return 1e3 * inside / len(boundaries)
    read.__name__ = part.replace(".", "_") + "_ms"
    return _reader(read)


boundary_drain_ms = _boundary_part("boundary.drain")
boundary_snapshot_ms = _boundary_part("boundary.snapshot")
boundary_checkpoint_ms = _boundary_part("boundary.checkpoint")


@_reader
def server_update_ms(run):
    updates = load(run).closed("learner.update")
    if not updates:
        return None
    return 1e3 * sum(b - a for a, b, _, _ in updates) / len(updates)


# -- the trainer thread's own account ---------------------------------
@_reader
def dispatch_thread_share(run):
    log = load(run)
    tid = log.trainer_tid()
    if tid is None:
        return None
    return 100.0 * log.clipped_s("trainer.update", tid) / run.window_s


@_reader
def trainer_untracked_share(run):
    log = load(run)
    tid = log.trainer_tid()
    if tid is None or not log.spans("trainer.handoff", tid):
        return None     # a program that does not account for its thread
    tracked = sum(log.clipped_s(name, tid) for name in TRAINER_THREAD)
    return 100.0 * (1.0 - tracked / run.window_s)


# -- the fused step's phases ------------------------------------------
def _step_phase(phase):
    def read(run):
        t0 = time.perf_counter()
        profile = run.probes.trainer.step_profile()
        if "step_profile_seconds" not in run.notes:
            # the first of the six readers pays for the one capture
            run.notes["step_profile_seconds"] = round(
                time.perf_counter() - t0, 3)
            if profile:
                run.notes["step_profile"] = (
                    f"{profile['steps']} steps of {profile['step_ms']:.4f} "
                    f"ms; of unscoped, no op ran for "
                    f"{profile.get('op_gap_ms', 0.0):.4f} ms and the text "
                    f"lacks {profile.get('unmatched_ms', 0.0):.4f} ms")
        return profile["phases"][phase] if profile else None
    read.__name__ = "step_" + phase + "_ms"
    return _reader(read)


step_gather_ms = _step_phase("gather")
step_forward_ms = _step_phase("forward")
step_targets_ms = _step_phase("targets")
step_backward_ms = _step_phase("backward")
step_optimizer_ms = _step_phase("optimizer")
step_unscoped_ms = _step_phase("unscoped")
