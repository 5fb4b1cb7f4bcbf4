"""Per-layer metrics read from the trainer thread's in-flight ledger
(``handyrl_tpu/telemetry/inflight.py``, PR 40), as the program's span
log holds it (``program_spans.load``: the learner's records on the
harness's clock, the window ``[lo, hi)``):

  ``device.starved``   a stretch in which the device had no step,
                       from the poll that found none to the return of
                       the next dispatch; widened at its start by the
                       attr ``since_ms`` it is the upper bound of the
                       stretch, less its part inside ``trainer.update``
                       (the device began its step somewhere in that
                       dispatch) the lower one
  ``trainer.update``,  with the attrs ``depth`` (steps in flight at the
  ``ingest.append``    span's entry) and ``done``

Everything is clipped to the window and read on the trainer thread (the
one that dispatches the steps) unless said.  No reader raises: a log
with no such span or attr (the parent of PR 40, telemetry off) reads
None and the run still prints its line.
"""

from .layers import percentile
from .program_spans import _reader, load

STARVED = "device.starved"
UPDATE = "trainer.update"
APPEND = "ingest.append"
INGEST = ("trainer.ingest",)
BOUNDARY = ("trainer.boundary", "trainer.handoff")
MIN_UNHELD = 20    # spans a median of unheld dispatches needs
# Which calls the runtime's queue cannot have held, by the depth they
# entered at, as shares of the deepest queue the log saw (32 steps on a
# v5e).  ISSUE 40 asked for "two under the deepest"; the chip read calls
# held from 27 of 32 (a step's wait at any depth from there), so the
# rule is half the deepest.  A dispatch into a queue that is nearly
# EMPTY is another thing again (3.1 ms where 1.8 is the rule in
# `geese.fed`: PERF.md, PR 40): the dispatch's own cost is read between
# an eighth and a half; the notes carry the other two classes.
FULL_FROM = 2         # held: depth over deepest // FULL_FROM
EMPTY_UNDER = 8       # nearly empty: depth under deepest // EMPTY_UNDER


def _overlap(a, b, spans):
    """Seconds of ``[a, b)`` inside ``spans`` (which do not overlap one
    another: the trainer thread's top-level spans)."""
    return sum(max(0.0, min(b, e) - max(a, s)) for s, e, _, _ in spans)


def starved_seconds(log, within=()):
    """``(lower, upper)`` seconds of the window the device was starved
    for: the ``device.starved`` spans, and the same widened by their
    ``since_ms``; None where the log has no such span.  With ``within``
    (names of top-level spans of the trainer thread), only the part the
    thread spent inside those."""
    starved = log.spans(STARVED)
    if not starved:
        return None
    tid = log.trainer_tid()
    cover = [s for name in within for s in log.spans(name, tid)]

    def part(a, b):
        a, b = max(a, log.lo), min(b, log.hi)
        if b <= a:
            return 0.0
        return _overlap(a, b, cover) if within else b - a

    # a span that begins at the window's close or after it is widened
    # into no part of the window: the harness closes that edge by
    # waiting the queue out inside a dispatch, and the seconds of that
    # wait are the first span's ``since_ms`` after it
    return (sum(part(a, b) for a, b, _, _ in starved),
            sum(part(a - 1e-3 * float(attrs.get("since_ms", 0.0)), b)
                for a, b, _, attrs in starved if a < log.hi))


def _starved_share(name, within=()):
    def read(run):
        log = load(run)
        seconds = starved_seconds(log, within)
        if seconds is None:
            return None
        spans, upper = (100.0 * s / run.window_s for s in seconds)
        # the bracket: the device went idle somewhere before each span,
        # and began its next step somewhere inside the dispatch that
        # closed it (a dispatch can return long after: 0.7 s, the first
        # after a boundary of `trinity.fed`)
        run.notes[name + "_upper"] = round(upper, 4)
        if not within:
            inside = starved_seconds(log, (UPDATE,))[0]
            run.notes[name + "_lower"] = round(
                spans - 100.0 * inside / run.window_s, 4)
        return spans
    read.__name__ = name
    return _reader(read)


window_starved_share = _starved_share("window_starved_share")
starved_in_ingest_share = _starved_share("starved_in_ingest_share", INGEST)
starved_in_boundary_share = _starved_share(
    "starved_in_boundary_share", BOUNDARY)


def _depths(log):
    """``depth`` of the dispatches that closed in the window."""
    return [int(s[3]["depth"])
            for s in log.closed(UPDATE, log.trainer_tid())
            if "depth" in s[3]]


@_reader
def run_ahead_steps_p10(run):
    depths = _depths(load(run))
    return percentile(depths, 10) if depths else None


@_reader
def run_ahead_ms_p50(run):
    log = load(run)
    depths = _depths(log)
    if not depths:
        return None
    # both of the window's edges are closed with the device caught up:
    # the steps dispatched in it are the steps it finished
    per_step = run.window_s / len(log.closed(UPDATE, log.trainer_tid()))
    return 1e3 * percentile(depths, 50) * per_step


def _by_depth(log, name, tid=None):
    """``(deepest, [(depth, start, end), ...])`` of the log's ``name``
    spans that carry a depth; the deepest over dispatches and appends
    alike, set-up's too, any thread's."""
    deepest = max((int(s[3]["depth"]) for kind in (UPDATE, APPEND)
                   for s in log.spans(kind) if "depth" in s[3]), default=None)
    return deepest, [(int(attrs["depth"]), a, b)
                     for a, b, _, attrs in log.spans(name, tid)
                     if "depth" in attrs]


def _unheld_median(log, name):
    """Median seconds of the log's ``name`` spans (set-up's too, any
    thread's: priming appends from the main thread) that the queue
    cannot have held, and how many there were.  A dispatch is also not
    counted where it found the queue nearly empty."""
    deepest, spans = _by_depth(log, name)
    if deepest is None:
        return None, 0
    floor = deepest // EMPTY_UNDER if name == UPDATE else 0
    free = [b - a for depth, a, b in spans
            if floor <= depth <= deepest // FULL_FROM]
    return (percentile(free, 50) if free else None), len(free)


@_reader
def dispatch_ms_per_step(run):
    log = load(run)
    median, count = _unheld_median(log, UPDATE)
    if count < MIN_UNHELD:
        if count:
            run.notes["dispatch_ms_per_step_unheld_spans"] = count
        return None
    deepest, spans = _by_depth(log, UPDATE)
    for label, members in (
            ("queue_nearly_empty", [b - a for d, a, b in spans
                                    if d < deepest // EMPTY_UNDER]),
            ("queue_over_half", [b - a for d, a, b in spans
                                 if d > deepest // FULL_FROM])):
        if members:
            run.notes[f"dispatch_ms_{label}"] = (
                f"{1e3 * percentile(members, 50):.4f} over {len(members)} "
                f"spans of the log (deepest queue {deepest})")
    return 1e3 * median


@_reader
def queue_wait_thread_share(run):
    log = load(run)
    tid = log.trainer_tid()
    waited = 0.0
    for name in (UPDATE, APPEND):
        median, count = _unheld_median(log, name)
        if median is None or (name == UPDATE and count < MIN_UNHELD):
            return None
        deepest, spans = _by_depth(log, name, tid)
        for depth, a, b in spans:
            lo, hi = max(a, log.lo), min(b, log.hi)
            # of a call that entered over half the deepest queue, what
            # lies in the window beyond the median of its kind's unheld
            # calls: only such a call can have stood in the queue
            if depth > deepest // FULL_FROM and hi > lo:
                waited += max(0.0, (hi - lo) - median)
        run.notes[f"queue_wait_unheld_{name.split('.')[1]}_ms"] = round(
            1e3 * median, 4)
    return 100.0 * waited / run.window_s
