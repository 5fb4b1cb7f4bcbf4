"""The trainer thread's in-flight ledger: how far ahead of the device
the thread runs, and when the device had no step to run.

JAX returns from a dispatch before the device has run it, so the
thread that dispatches the steps does not know where the device is.
This ledger is the one fact it lacks.  Where the thread dispatches a
step it hands over a TOKEN — a scalar output of that step which no
later call donates (a leaf of its ``metrics``) — and at edges it
already has it POLLS: tokens are popped from the head of a FIFO while
``token.is_ready()`` (non-blocking; steps finish in order, so a poll
costs what has finished, not what is in flight).  What is left is the
run-ahead depth.

What it records, all through :mod:`.spans`:

  * ``device.starved`` — from the first poll that found nothing in
    flight to the next launch's return, with ``at`` (the site of that
    poll, a literal the caller passes) and ``since_ms`` (how long
    before it the previous poll was).  The device went idle somewhere
    in ``[ts - since_ms, ts]``: the span widened by ``since_ms`` is the
    upper bound of the stretch in which the device had no step.  It
    began its next step somewhere inside the dispatch that closed the
    span (``trainer.update`` ends where the span does): the span less
    that dispatch is the lower bound.  It is recorded after the fact and
    so has no ``hrl:`` mirror; it lies on the telemetry clock beside
    the trainer thread's other spans, whose mirrors are in every trace.
  * ``depth`` / ``done`` on a span a caller wraps in :meth:`watch`
    (``trainer.update``, ``ingest.append``): steps in flight at its
    entry, and steps seen to finish between its entry and its exit.
  * per epoch (:meth:`epoch`): the seconds the device was starved, the
    median depth at launch, and the seconds with a step in flight.

One thread feeds and polls it; it starts none.  With telemetry off it
holds no token and every entry is a constant-time no-op.  Nothing here
imports jax.
"""

import time
from collections import deque

from . import spans as _spans

STARVED = "device.starved"


class _Watch:
    """``with ledger.watch(site, attrs):`` — polls at both edges and
    writes ``depth`` and ``done`` into ``attrs`` (a span's own dict,
    read when the span closes)."""

    __slots__ = ("ledger", "at", "attrs", "seen")

    def __init__(self, ledger, at, attrs):
        self.ledger, self.at, self.attrs = ledger, at, attrs
        self.seen = 0

    def __enter__(self):
        self.attrs["depth"] = self.ledger.poll(self.at)
        self.seen = self.ledger.completed
        return self

    def __exit__(self, exc_type, exc, tb):
        self.ledger.poll(self.at)
        self.attrs["done"] = self.ledger.completed - self.seen
        return False


class InFlight:
    def __init__(self):
        self._tokens = deque()   # the steps in flight, oldest first
        self.completed = 0       # steps seen finished, ever
        self.dropped = 0         # tokens whose is_ready() raised
        self._last_poll = None   # stamp of the newest poll
        self._idle = None        # (t0, since, at) of an open starved stretch
        self._mark = None        # where the epoch's account begins
        self._starved = 0.0      # starved seconds closed since the mark
        self._depths = []        # depth at each launch since the mark

    def poll(self, at):
        """Pop what has finished; returns the steps still in flight.
        ``at`` names the caller's site: should this poll be the first to
        find nothing in flight, the starved stretch is ``at`` it."""
        tokens = self._tokens
        if not _spans.enabled():
            tokens.clear()       # telemetry went off under a live ledger
            return 0
        now = _spans.now()
        if self._mark is None:
            self._mark = now
        while tokens:
            try:
                if not tokens[0].is_ready():
                    break
                self.completed += 1
            except Exception:
                # a deleted or failed array: the step is not in flight,
                # and the thread that trains is no place to say more
                self.dropped += 1
            tokens.popleft()
        if not tokens and self._idle is None:
            last = self._last_poll
            self._idle = (now, 0.0 if last is None else now - last, at)
        self._last_poll = now
        return len(tokens)

    def launch(self, token, at="update"):
        """A step was dispatched and the call has returned: ``token``
        becomes ready when the device has run it.  Closes an open
        starved stretch."""
        if not _spans.enabled():
            return
        self._depths.append(len(self._tokens))   # as the entry poll left it
        self.poll(at)
        if self._idle is not None:
            (t0, since, where), self._idle = self._idle, None
            now = self._last_poll
            self._starved += now - max(t0, self._mark)
            _spans.record_span(STARVED, t0, now - t0,
                               since_ms=round(1e3 * since, 3), at=where)
        self._tokens.append(token)

    def watch(self, at, attrs):
        return _Watch(self, at, attrs)

    def drain(self, at, grain=5e-4):
        """Wait until nothing is in flight, polling every ``grain``
        seconds, so that the stretch that starts here is known to the
        grain (a blocking fetch would say only that the device went
        idle somewhere inside it).  Returns at once with telemetry
        off: the caller's own fetch then does the waiting."""
        while self.poll(at):
            time.sleep(grain)

    def epoch(self):
        """The account since the last call: ``starved_sec`` (an open
        stretch counted up to now, the rest of it in the next account),
        ``run_ahead_p50`` (median depth at launch) and ``in_flight_sec``
        (the account's wall seconds less the starved ones).  All None
        with telemetry off or before the first poll."""
        if not _spans.enabled() or self._mark is None:
            return {"starved_sec": None, "run_ahead_p50": None,
                    "in_flight_sec": None}
        now = _spans.now()
        starved = self._starved
        if self._idle is not None:
            starved += now - max(self._idle[0], self._mark)
        depths = sorted(self._depths)
        out = {"starved_sec": round(starved, 4),
               "run_ahead_p50": depths[len(depths) // 2] if depths else 0,
               "in_flight_sec": round(now - self._mark - starved, 4)}
        self._starved, self._depths, self._mark = 0.0, [], now
        return out
