"""Telemetry: spans, trace-context propagation, the flight recorder,
the Perfetto exporter, and the status endpoint.

The propagation tests are the PR's protocol contract: a framed round
trip carries trace ids across a live pipe, a pre-envelope peer (raw
``(verb, payload)``) still interoperates, and the flight-recorder ring
evicts oldest-first under an injectable clock.  All deterministic, no
sleeps on the assert path."""

import json
import multiprocessing as mp
import os
import urllib.request

import pytest

from handyrl_tpu import telemetry
from handyrl_tpu.analysis.guards import StallWatchdog
from handyrl_tpu.connection import (
    QueueCommunicator,
    TracedConnection,
)
from handyrl_tpu.telemetry.export import build_trace, collect_run


@pytest.fixture(autouse=True)
def _reset_telemetry():
    """Every test starts from a disarmed state and leaves one behind
    (the module state is process-global)."""
    telemetry.configure(enabled=False)
    yield
    telemetry.configure(enabled=False)


def _ticker(start=0.0, step=1.0):
    t = {"now": start}

    def clock():
        t["now"] += step
        return t["now"]

    return clock


# -- spans --------------------------------------------------------------

def test_trace_span_records_against_injectable_clock():
    telemetry.configure(enabled=True, clock=_ticker())
    with telemetry.trace_span("work", k="v"):
        pass
    spans = telemetry.stats()["ring_spans"]
    assert spans == 1
    # the ring holds the record with the injected timestamps
    rec = list(telemetry.spans._state.ring)[0]
    assert rec["name"] == "work"
    assert rec["dur"] == pytest.approx(1.0)  # one clock tick inside
    assert rec["attrs"] == {"k": "v"}


def test_disabled_telemetry_records_nothing_and_wraps_nothing():
    telemetry.configure(enabled=False)
    with telemetry.trace_span("work"):
        pass
    assert telemetry.stats()["ring_spans"] == 0
    assert telemetry.maybe_trace() is None
    msg = ("episode", {"x": 1})
    assert telemetry.wrap_trace(msg) is msg  # wire format untouched


def test_sample_rate_zero_never_traces():
    telemetry.configure(enabled=True, sample_rate=0.0)
    assert all(telemetry.maybe_trace() is None for _ in range(32))


def test_span_log_file_written_and_flushed(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path),
                        role="learner")
    for i in range(3):
        with telemetry.trace_span(f"s{i}"):
            pass
    telemetry.flush()
    files = [f for f in os.listdir(tmp_path) if f.startswith("spans-")]
    assert len(files) == 1
    with open(tmp_path / files[0]) as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines[0]["meta"]["role"] == "learner"
    assert [r["name"] for r in lines[1:]] == ["s0", "s1", "s2"]


# -- trace context over the wire ---------------------------------------

def test_envelope_round_trip_carries_ids_across_a_live_pipe():
    telemetry.configure(enabled=True)
    a, b = mp.get_context("spawn").Pipe(duplex=True)
    try:
        sender, receiver = TracedConnection(a), TracedConnection(b)
        ctx = telemetry.new_trace()
        telemetry.set_trace(ctx)
        sender.send(("episode", {"steps": 9}))
        telemetry.clear_trace()
        assert telemetry.current_trace() is None
        msg = receiver.recv()
        # the payload arrives intact AND the sender's context is
        # adopted into the receiving thread
        assert msg == ("episode", {"steps": 9})
        assert telemetry.current_trace() == ctx
        # the reply direction works the same way
        receiver.send(("ack", None))
        telemetry.clear_trace()
        assert sender.recv() == ("ack", None)
        assert telemetry.current_trace() == ctx
    finally:
        a.close()
        b.close()


def test_pre_envelope_peer_interoperates():
    """A raw (verb, payload) from a peer that predates the envelope
    passes through unchanged — and clears any stale context instead of
    letting it bleed into unrelated spans."""
    telemetry.configure(enabled=True)
    a, b = mp.get_context("spawn").Pipe(duplex=True)
    try:
        receiver = TracedConnection(b)
        telemetry.set_trace(telemetry.new_trace())  # stale context
        a.send(("args", None))                      # raw, no envelope
        assert receiver.recv() == ("args", None)
        assert telemetry.current_trace() is None
        # and an untraced TracedConnection sender IS a raw peer
        TracedConnection(a).send(("beat", {"n": 1}))
        assert b.recv() == ("beat", {"n": 1})       # raw on the wire
    finally:
        a.close()
        b.close()


def test_queue_communicator_codecs_at_the_handling_thread():
    """The learner/gather hubs codec at their queue boundaries: the
    reply enqueued while a request's context is current carries it."""
    telemetry.configure(enabled=True)
    ours, theirs = mp.get_context("spawn").Pipe(duplex=True)
    hub = QueueCommunicator([ours])
    worker = TracedConnection(theirs)
    try:
        ctx = telemetry.new_trace()
        telemetry.set_trace(ctx)
        worker.send(("episode", {"steps": 3}))
        telemetry.clear_trace()
        conn, (verb, payload) = hub.recv(timeout=5)
        assert (verb, payload) == ("episode", {"steps": 3})
        assert telemetry.current_trace() == ctx  # adopted HERE
        hub.send(conn, None)                     # reply carries ctx
        telemetry.clear_trace()
        assert worker.recv() is None
        assert telemetry.current_trace() == ctx
    finally:
        hub.shutdown()
        ours.close()
        theirs.close()


def test_payload_trace_adopts_stamped_context():
    telemetry.configure(enabled=True)
    ctx = telemetry.new_trace()
    with telemetry.payload_trace({"trace": ctx, "steps": 1}):
        assert telemetry.current_trace() == tuple(ctx)
    assert telemetry.current_trace() is None
    with telemetry.payload_trace({"steps": 1}):  # unstamped: no-op
        assert telemetry.current_trace() is None


# -- flight recorder ----------------------------------------------------

def test_ring_evicts_oldest_first_under_injectable_clock(tmp_path):
    clock = _ticker()
    telemetry.configure(enabled=True, ring=4, log_dir=str(tmp_path),
                        primary=True, clock=clock)
    for i in range(7):
        telemetry.add_event(f"e{i}")
    path = telemetry.dump("test")
    with open(path) as f:
        doc = json.load(f)
    names = [s["name"] for s in doc["spans"]]
    assert names == ["e3", "e4", "e5", "e6"]  # oldest evicted first
    ts = [s["ts"] for s in doc["spans"]]
    assert ts == sorted(ts)  # ring order is time order
    assert doc["reason"] == "test"


def test_forced_stall_produces_exactly_one_dump(tmp_path):
    """The repo-gate contract: one induced stall = one flight-recorder
    dump, with the stall event in the ring — driven entirely through
    an injectable clock (the watchdog's and the recorder's)."""
    telemetry.configure(enabled=True, ring=64, log_dir=str(tmp_path),
                        primary=True)
    t = [0.0]
    dog = StallWatchdog(max_stall_seconds=10.0, clock=lambda: t[0])
    dog.on_stall = telemetry.stall_hook
    dog.beat("server")
    dog.beat("recv_loop")
    t[0] = 5.0
    assert dog.sample() == 0                  # within budget: no dump
    assert telemetry.dump_count() == 0
    t[0] = 11.0
    dog.beat("recv_loop")                     # one loop stays healthy
    assert dog.sample() == 1                  # server NEWLY stalled
    assert telemetry.dump_count() == 1        # exactly one dump
    assert dog.sample() == 0                  # still stalled: no re-dump
    assert telemetry.dump_count() == 1
    with open(tmp_path / "flightrec.json") as f:
        doc = json.load(f)
    assert doc["reason"] == "stall_event"
    stalls = [s for s in doc["spans"] if s["name"] == "stall"]
    assert len(stalls) == 1
    assert stalls[0]["attrs"]["loop"] == "server"


def test_crash_dump_writes_flightrec(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path),
                        primary=True)
    telemetry.crash_dump("trainer", RuntimeError("boom"))
    with open(tmp_path / "flightrec.json") as f:
        doc = json.load(f)
    assert doc["reason"] == "crash"
    assert any(s["name"] == "crash" for s in doc["spans"])


def test_dump_without_run_dir_is_a_noop():
    telemetry.configure(enabled=True, log_dir=None)
    assert telemetry.dump("test") is None
    assert telemetry.dump_count() == 0


# -- exporter -----------------------------------------------------------

def test_exporter_builds_perfetto_loadable_events(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path),
                        role="learner")
    ctx = telemetry.new_trace()
    telemetry.set_trace(ctx)
    telemetry.record_span("rpc.episode", 1.0, 0.25)
    telemetry.add_event("episode.intake")
    telemetry.clear_trace()
    telemetry.flush()
    roles, spans = collect_run(str(tmp_path))
    assert roles == {os.getpid(): "learner"}
    doc = build_trace(spans, roles)
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "learner"
    complete = [e for e in events if e["ph"] == "X"]
    assert complete[0]["name"] == "rpc.episode"
    assert complete[0]["ts"] == pytest.approx(1.0e6)   # us
    assert complete[0]["dur"] == pytest.approx(0.25e6)
    assert complete[0]["args"]["trace"] == format(ctx[0], "x")
    instant = [e for e in events if e["ph"] == "i"]
    assert instant[0]["name"] == "episode.intake"
    json.dumps(doc)  # serializable end to end


def test_exporter_merges_processes_by_trace_id():
    """Two processes' span records sharing one propagated trace id end
    up in one document, distinguishable by pid — the cross-process
    property the e2e drive asserts on real logs."""
    spans = [
        {"name": "episode.rollout", "ts": 1.0, "dur": 0.5, "pid": 11,
         "tid": 1, "trace": 0xabc, "parent": 1},
        {"name": "rpc.episode", "ts": 2.0, "dur": 0.1, "pid": 22,
         "tid": 2, "trace": 0xabc, "parent": 2},
    ]
    doc = build_trace(spans, {11: "worker-0", 22: "learner"})
    traced = [e for e in doc["traceEvents"]
              if e.get("args", {}).get("trace") == "abc"]
    assert {e["pid"] for e in traced} == {11, 22}


# -- policy-lag reduction ----------------------------------------------

def test_summarize_lags():
    out = telemetry.summarize_lags([0, 0, 1, 1, 2, 8])
    assert out["policy_lag_mean"] == pytest.approx(2.0)
    assert out["policy_lag_max"] == 8.0
    assert out["policy_lag_p95"] == 8.0
    empty = telemetry.summarize_lags([])
    assert empty == {"policy_lag_mean": 0.0, "policy_lag_p95": 0.0,
                     "policy_lag_max": 0.0}
    ones = telemetry.summarize_lags([1] * 100)
    assert ones["policy_lag_p95"] == 1.0


# -- status endpoint ----------------------------------------------------

def test_status_endpoint_serves_live_json():
    from handyrl_tpu.telemetry.status import StatusServer

    calls = {"n": 0}

    def snapshot():
        calls["n"] += 1
        return {"epoch": 7, "fleet": {"fleet_size": 2}}

    server = StatusServer(0, snapshot)  # port 0: OS-assigned
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/", timeout=5) as resp:
            assert resp.status == 200
            doc = json.loads(resp.read())
        assert doc == {"epoch": 7, "fleet": {"fleet_size": 2}}
        assert calls["n"] == 1
    finally:
        server.close()


def test_healthz_answers_without_the_snapshot():
    """GET /healthz is the load-balancer/supervision liveness probe:
    200 + a constant tiny JSON, WITHOUT invoking the snapshot callable
    (a high-frequency poller must not pay — or race — full snapshot
    assembly), while / keeps serving the full document."""
    from handyrl_tpu.telemetry.status import StatusServer

    calls = {"n": 0}

    def snapshot():
        calls["n"] += 1
        return {"epoch": 1}

    server = StatusServer(0, snapshot)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz",
                timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"] == "application/json"
            assert json.loads(resp.read()) == {"ok": True}
        assert calls["n"] == 0          # liveness never built a snapshot
        # query strings route the same way; the full page still works
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz?probe=1",
                timeout=5) as resp:
            assert json.loads(resp.read()) == {"ok": True}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/", timeout=5) as resp:
            assert json.loads(resp.read()) == {"epoch": 1}
        assert calls["n"] == 1
    finally:
        server.close()


# -- the in-flight ledger (telemetry/inflight.py) -------------------------

class _Clock:
    """A clock the test moves by hand."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class _Token:
    """What the ledger asks of a step's output: ``is_ready()``."""

    def __init__(self, ready=False, error=None):
        self.ready, self.error, self.asked = ready, error, 0

    def is_ready(self):
        self.asked += 1
        if self.error is not None:
            raise self.error
        return self.ready


def _ledger(clock=None):
    clock = clock or _Clock()
    telemetry.configure(enabled=True, clock=clock)
    return telemetry.InFlight(), clock


def _starved():
    return [r for r in telemetry.ring_snapshot()
            if r["name"] == "device.starved"]


def test_the_ledger_pops_finished_steps_from_the_head_in_order():
    ledger, clock = _ledger()
    a, b, c = _Token(), _Token(), _Token()
    for token in (a, b, c):
        ledger.launch(token)
    assert ledger.poll("update") == 3
    # steps finish in order: a ready step BEHIND an unready one stays
    b.ready = True
    asked = c.asked
    assert ledger.poll("update") == 3 and ledger.completed == 0
    assert c.asked == asked           # a poll costs what finished, no more
    a.ready = True
    assert ledger.poll("update") == 1 and ledger.completed == 2
    c.ready = True
    assert ledger.poll("update") == 0 and ledger.completed == 3


@pytest.mark.parametrize("site", ["update", "ingest.decompress",
                                  "boundary.drain"])
def test_a_starved_span_runs_from_the_poll_that_found_nothing_to_the_launch(
        site):
    ledger, clock = _ledger()
    ledger.poll("update")             # the run's first poll: never launched
    clock.t = 101.0
    first = _Token()
    ledger.launch(first)              # closes the stretch before step one
    clock.t = 102.0
    assert ledger.poll("update") == 1     # in flight: nothing starts
    first.ready = True
    clock.t = 102.5
    if site == "boundary.drain":
        ledger.drain(site)            # (c) the boundary waited the queue out
    else:
        # (a) found empty at the dispatch's entry, (b) between two
        # episodes of ingest.decompress
        assert ledger.poll(site) == 0
    clock.t = 103.0
    ledger.poll("ingest.append")      # a later poll moves nothing
    clock.t = 104.0
    ledger.launch(_Token())
    before, span = _starved()
    assert before["ts"] == 100.0 and before["dur"] == 1.0
    assert before["attrs"] == {"since_ms": 0.0, "at": "update"}
    assert span["ts"] == 102.5 and span["dur"] == pytest.approx(1.5)
    # the device went idle somewhere in the half second before ts
    assert span["attrs"] == {"since_ms": 500.0, "at": site}


def test_a_queue_that_never_emptied_records_no_span():
    ledger, clock = _ledger()
    tokens = [_Token() for _ in range(6)]
    ledger.poll("update")
    ledger.launch(tokens[0])
    (first,) = _starved()             # the stretch before step one only
    for k, token in enumerate(tokens[1:], 1):
        clock.t += 1.0
        ledger.launch(token)          # queued behind the step before ...
        tokens[k - 1].ready = True    # ... which then finishes
        assert ledger.poll("ingest.decompress") == 1
    assert _starved() == [first]


def test_a_queue_that_ran_dry_inside_a_dispatch_is_a_span_of_no_length():
    ledger, clock = _ledger()
    ledger.poll("update")
    one = _Token()
    ledger.launch(one)
    clock.t = 101.0
    assert ledger.poll("update") == 1     # in flight at the entry
    one.ready = True                      # finishes while the call runs
    clock.t = 101.004
    ledger.launch(_Token())
    span = _starved()[-1]
    # nothing says how long the device waited: at least 0, at most 4 ms
    assert span["ts"] == 101.004 and span["dur"] == 0.0
    assert span["attrs"] == {"since_ms": 4.0, "at": "update"}


def test_watch_writes_depth_at_entry_and_steps_done_by_exit():
    ledger, clock = _ledger()
    tokens = [_Token() for _ in range(4)]
    for token in tokens:
        ledger.launch(token)
    tokens[0].ready = True
    attrs = {}
    with ledger.watch("ingest.append", attrs):
        assert attrs == {"depth": 3}      # the entry poll popped one
        tokens[1].ready = tokens[2].ready = True
    assert attrs == {"depth": 3, "done": 2}


def test_the_ledger_holds_no_token_with_telemetry_off():
    telemetry.configure(enabled=False)
    ledger = telemetry.InFlight()
    token = _Token()
    attrs = {}
    with ledger.watch("update", attrs):
        ledger.launch(token)
    ledger.drain("boundary.drain")
    assert len(ledger._tokens) == 0 and token.asked == 0
    assert attrs == {"depth": 0, "done": 0}
    assert ledger.epoch() == {"starved_sec": None, "run_ahead_p50": None,
                              "in_flight_sec": None}
    assert telemetry.ring_snapshot() == []
    # and one that was live lets go of what it held
    ledger, _ = _ledger()
    ledger.launch(_Token())
    telemetry.configure(enabled=False)
    assert ledger.poll("update") == 0 and len(ledger._tokens) == 0


def test_a_token_that_raises_is_dropped_and_counted_never_raised():
    ledger, clock = _ledger()
    bad = _Token(error=RuntimeError("Array has been deleted"))
    good = _Token(ready=True)
    ledger.launch(bad)
    ledger.launch(good)               # launch polls: no raise here either
    assert ledger.poll("update") == 0
    assert ledger.dropped == 1 and ledger.completed == 1


def test_an_epochs_account_splits_an_open_stretch_at_the_boundary():
    ledger, clock = _ledger()
    ledger.poll("update")             # 100: the account begins, starved
    clock.t = 102.0
    one = _Token()
    ledger.launch(one)                # 2 s starved (the compile)
    clock.t = 105.0
    two = _Token()
    ledger.launch(two)                # depth 1 at this launch
    one.ready = two.ready = True
    clock.t = 108.0
    ledger.drain("boundary.drain")    # runs dry at 108
    clock.t = 109.0
    first = ledger.epoch()
    assert first == {"starved_sec": 3.0, "run_ahead_p50": 1,
                     "in_flight_sec": 6.0}
    clock.t = 111.0
    ledger.launch(_Token())           # the stretch closes in epoch two
    clock.t = 112.0
    second = ledger.epoch()
    # of the stretch's 3 s, the one before the mark was counted already
    assert second == {"starved_sec": 2.0, "run_ahead_p50": 0,
                      "in_flight_sec": 1.0}
    assert _starved()[-1]["dur"] == pytest.approx(3.0)


def test_drain_polls_until_the_queue_is_empty(monkeypatch):
    ledger, clock = _ledger()
    token = _Token()
    ledger.launch(token)
    naps = []

    def nap(seconds):
        naps.append(seconds)
        clock.t += seconds
        if len(naps) == 3:
            token.ready = True

    monkeypatch.setattr("handyrl_tpu.telemetry.inflight.time.sleep", nap)
    clock.t = 101.0
    ledger.drain("boundary.drain")
    assert len(naps) == 3 and ledger.poll("update") == 0
    ledger.launch(_Token())
    span = _starved()[-1]
    # known to the grain of the polling, not to the length of the wait
    assert span["attrs"]["since_ms"] == pytest.approx(1e3 * naps[-1])
    assert span["attrs"]["at"] == "boundary.drain"


def test_section_timers_hand_their_span_the_attrs_filled_in_the_block():
    from handyrl_tpu.utils.profiling import SectionTimers

    telemetry.configure(enabled=True, clock=_ticker())
    timers = SectionTimers()
    attrs = {}
    with timers.section("update", attrs=attrs):
        attrs["depth"] = 7
    with timers.section("batch_wait"):
        pass
    update, wait = telemetry.ring_snapshot()
    assert update["name"] == "trainer.update"
    assert update["attrs"] == {"depth": 7}
    assert "attrs" not in wait
    assert timers.snapshot()["update"]["n"] == 1


def test_the_exporter_gives_a_starved_stretch_a_track_of_its_own():
    recs = [{"name": "trainer.update", "ts": 1.0, "dur": 0.5, "pid": 9,
             "tid": 77},
            {"name": "device.starved", "ts": 1.2, "dur": 0.6, "pid": 9,
             "tid": 77, "attrs": {"since_ms": 1.0, "at": "update"}}]
    update, starved = build_trace(recs)["traceEvents"]
    # it begins inside one span of the thread and ends after it: on the
    # thread's own track the viewer could not nest it
    assert update["tid"] == 77 and starved["tid"] == 0
    assert starved["args"] == {"since_ms": 1.0, "at": "update"}


def test_the_ledger_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; import handyrl_tpu.telemetry.inflight; "
            "sys.exit(int(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules)))")
    # the package's __init__ imports what it imports; the module itself
    # must not add jax to it
    base = ("import sys; import handyrl_tpu.telemetry.spans; "
            "sys.exit(int(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules)))")
    assert (subprocess.run([sys.executable, "-c", code]).returncode
            == subprocess.run([sys.executable, "-c", base]).returncode)
