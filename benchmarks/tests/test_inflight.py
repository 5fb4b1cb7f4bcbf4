"""The readers of the trainer thread's in-flight ledger
(``harness/inflight.py``) on a synthetic span log: each one's
arithmetic, the window's clipping at both edges, the bracket note, the
fewer-than-twenty rule, and None — never an exception — on a log the
parent of PR 40 would have written."""

import importlib.util
import os
import types

import pytest

from benchmarks.harness import inflight
from benchmarks.harness import program_spans as ps
from benchmarks.harness.cells import BENCH_DIR, load_manifest

from test_program_spans import MAIN, OFFSET, PID, _rec, _run, _write

ALL = ["geese.fed", "geister.fed", "trinity.fed", "joyai.fed"]
NEW = {
    "window_starved_share": ("%", "lower", "device", ALL),
    "starved_in_ingest_share": ("%", "lower", "ring ingest", ALL),
    "starved_in_boundary_share": ("%", "lower", "conductor", ALL[:2]),
    "run_ahead_steps_p10": ("steps", "higher", "fused step", ALL),
    "run_ahead_ms_p50": ("ms", "lower", "conductor", ALL),
    "dispatch_ms_per_step": ("ms", "lower", "fused step", ALL),
    "queue_wait_thread_share": ("%", "higher", "fused step", ALL),
}


def _recorded(free_updates=24):
    """A window of 10 s, [100, 110) on the telemetry clock.  The queue
    is 30 deep when full; a dispatch costs 1 ms unheld, 20 ms held."""
    recs = []
    # set-up: priming's appends from the main thread with nothing in
    # flight, and the run-ahead building up
    recs += [_rec("ingest.append", 80.0 + k, 0.004, tid=MAIN, depth=0,
                  done=0, wait_ms=[1.0]) for k in range(3)]
    recs += [_rec("trainer.update", 90.0 + 0.01 * k, 0.001, depth=k, done=0)
             for k in range(free_updates)]
    # a stretch the window's opening edge cuts: 0.5 s of it inside,
    # its bracket wholly outside
    recs.append(_rec("device.starved", 99.0, 1.5, since_ms=400.0,
                     at="update"))
    # the window: 100 held dispatches, depth 30 at entry but for ten
    recs += [_rec("trainer.update", 101.0 + 0.02 * k, 0.02,
                  depth=30 if k % 10 else 12, done=1) for k in range(100)]
    # an ingest call during which the queue ran dry, found between two
    # episodes' unpacking: 0.1 s of the stretch inside trainer.ingest,
    # 0.05 s after it, inside the next dispatch
    recs += [_rec("trainer.ingest", 104.0, 0.3, episodes=8),
             _rec("ingest.decompress", 104.0, 0.25),
             _rec("ingest.append", 104.26, 0.03, depth=0, done=0,
                  wait_ms=[5.0]),
             _rec("device.starved", 104.2, 0.15, since_ms=40.0,
                  at="ingest.decompress"),
             _rec("trainer.update", 104.3, 0.05, depth=0, done=0)]
    # a held append: 24 ms where an unheld one takes 4
    recs += [_rec("trainer.ingest", 106.0, 0.03, episodes=1),
             _rec("ingest.append", 106.0, 0.024, depth=29, done=1,
                  wait_ms=[30.0])]
    # a boundary: the drain leaves nothing in flight, the stretch lasts
    # through the boundary (0.2 s of it) and the hand-over (0.1 s) into
    # the next epoch's first dispatch (0.01 s)
    recs += [_rec("trainer.boundary", 107.0, 0.4),
             _rec("boundary.drain", 107.0, 0.2),
             _rec("trainer.handoff", 107.4, 0.1),
             _rec("device.starved", 107.2, 0.31, since_ms=0.5,
                  at="boundary.drain"),
             _rec("trainer.update", 107.5, 0.01, depth=0, done=0)]
    # a stretch the closing edge cuts: 0.2 s inside the window
    recs.append(_rec("device.starved", 109.8, 1.0, since_ms=100.0,
                     at="boundary.drain"))
    # the harness closes its window by waiting the queue out inside a
    # dispatch: the stretch that begins as that dispatch returns is not
    # the window's, nor are the 5 s before it in which the queue drained
    recs.append(_rec("device.starved", 110.0001, 0.0, since_ms=5000.0,
                     at="update"))
    return recs


@pytest.fixture()
def run(tmp_path):
    _write(tmp_path, _recorded())
    return _run(ps.read_log(str(tmp_path), OFFSET, 100.0 + OFFSET,
                            110.0 + OFFSET, pid=PID))


def test_the_starved_share_is_clipped_at_both_edges_with_its_bracket(run):
    # 0.5 (cut by the opening edge) + 0.15 + 0.31 + 0.2 (cut by the close)
    assert inflight.window_starved_share(run) == pytest.approx(
        100.0 * (0.5 + 0.15 + 0.31 + 0.2) / 10.0)
    # each span widened by its since_ms, the widening clipped too: the
    # first stretch's 0.4 s lie before the window and add nothing
    assert run.notes["window_starved_share_upper"] == pytest.approx(
        100.0 * (0.5 + 0.19 + 0.3105 + 0.3) / 10.0, abs=1e-3)
    # ... and less what lies inside the dispatches that closed them
    # (0.05 and 0.01 s): the device began its step somewhere in those
    assert run.notes["window_starved_share_lower"] == pytest.approx(
        100.0 * (0.5 + 0.10 + 0.30 + 0.2) / 10.0, abs=1e-3)


def test_the_starved_parts_are_split_by_the_threads_top_level_spans(run):
    total = inflight.window_starved_share(run)
    ingest = inflight.starved_in_ingest_share(run)
    boundary = inflight.starved_in_boundary_share(run)
    assert ingest == pytest.approx(100.0 * 0.1 / 10.0)
    assert boundary == pytest.approx(100.0 * (0.2 + 0.1) / 10.0)
    # what is left lay inside dispatches, or in no span of the thread:
    # the parts and the remainder add up to the whole
    assert 0 < total - ingest - boundary == pytest.approx(
        100.0 * (0.5 + 0.05 + 0.01 + 0.2) / 10.0)
    # the bracket of the ingest part grows by the 40 ms before it
    assert run.notes["starved_in_ingest_share_upper"] == pytest.approx(
        100.0 * 0.14 / 10.0)


def test_the_run_ahead_is_read_off_the_windows_dispatches(run):
    # 102 dispatches closed in the window: 90 at 30, ten at 12, two at 0
    assert inflight.run_ahead_steps_p10(run) == 12
    assert inflight.run_ahead_ms_p50(run) == pytest.approx(
        1e3 * 30 * 10.0 / 102)


def test_dispatch_is_the_median_of_calls_the_queue_cannot_have_held(run):
    # the deepest queue of the log is 30: unheld and not nearly empty is
    # a depth of 3 to 15.  Thirteen of set-up at 1 ms, ten of the window
    # at depth 12 and 20 ms: the median of the 23 is an unheld one
    assert inflight.dispatch_ms_per_step(run) == pytest.approx(1.0)
    # the two other classes, for the reader of the run's notes: depth
    # 0-2 of set-up and the window's two at 0; depth 16 and over
    assert run.notes["dispatch_ms_queue_nearly_empty"].startswith(
        "1.0000 over 5 spans")
    assert run.notes["dispatch_ms_queue_over_half"].startswith(
        "20.0000 over 98 spans")


def test_the_queue_wait_is_what_exceeds_the_unheld_median_of_each_kind(run):
    # only a call that entered over half the deepest queue can have
    # stood in it: the 90 dispatches at depth 30, (20 - 1) ms each (the
    # ten at 12 and the slow two at 0 waited for no queue), and the
    # append at 29, 24 ms where the unheld median is 4 (three of
    # priming and the one at depth 0)
    assert inflight.queue_wait_thread_share(run) == pytest.approx(
        100.0 * (90 * 0.019 + 0.020) / 10.0)
    assert run.notes["queue_wait_unheld_update_ms"] == pytest.approx(1.0)
    assert run.notes["queue_wait_unheld_append_ms"] == pytest.approx(4.0)
    # never more than the thread spent in the two kinds of span
    assert inflight.queue_wait_thread_share(run) <= (
        ps.dispatch_thread_share(run)
        + 100.0 * run._program_spans.clipped_s("ingest.append") / 10.0)


def test_under_twenty_unheld_dispatches_read_none_and_say_how_many(tmp_path):
    recs = [r for r in _recorded(free_updates=5)
            if not (r["name"] == "trainer.update"
                    and r.get("attrs", {}).get("depth") == 12)]
    _write(tmp_path, recs)
    few = _run(ps.read_log(str(tmp_path), OFFSET, 100.0 + OFFSET,
                           110.0 + OFFSET, pid=PID))
    assert inflight.dispatch_ms_per_step(few) is None
    assert few.notes["dispatch_ms_per_step_unheld_spans"] == 2
    assert inflight.queue_wait_thread_share(few) is None
    # the others do not need them
    assert inflight.run_ahead_steps_p10(few) == 30


def _read(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_manifest_names_a_reader_file_for_every_new_entry():
    entries = {m["name"]: m for m in load_manifest()["per_layer"]}
    for name, (unit, better, layer, cells) in NEW.items():
        entry = entries[name]
        assert (entry["unit"], entry["better"], entry["layer"],
                entry["workloads"]) == (unit, better, layer, cells), name
        assert entry["source"] == "program_span"
        assert entry["moves"] == "learner_frames_per_s"
        assert _read(name) is getattr(inflight, name)
    # appended: the entries the benchmark had come first, as they were
    assert [m["name"] for m in load_manifest()["per_layer"]][-7:] == list(NEW)


@pytest.mark.parametrize("name", list(NEW))
def test_every_reader_finds_a_number_on_the_synthetic_log(name, run):
    value = _read(name)(run)
    assert isinstance(value, (int, float)) and value > 0, name


@pytest.mark.parametrize("name", list(NEW))
def test_a_parents_log_reads_none_and_never_raises(name, tmp_path):
    read = _read(name)
    # no log at all (telemetry off, or the file missing)
    run = _run(None)
    assert read(run) is None
    # the parent's program: the same spans with no depth, no done, and
    # no device.starved among them
    old = []
    for rec in _recorded():
        if rec["name"] == "device.starved":
            continue
        attrs = {k: v for k, v in rec.get("attrs", {}).items()
                 if k not in ("depth", "done")}
        rec = {k: v for k, v in rec.items() if k != "attrs"}
        old.append(dict(rec, attrs=attrs) if attrs else rec)
    _write(tmp_path, old)
    run = _run(ps.read_log(str(tmp_path), OFFSET, 100.0 + OFFSET,
                           110.0 + OFFSET, pid=PID))
    assert read(run) is None
    assert not [k for k in run.notes if k.endswith("_unread")]
    # a window that holds nothing of the log
    empty = _run(ps.read_log(str(tmp_path), OFFSET, 500.0, 510.0, pid=PID))
    assert read(empty) is None
    # a run object without a log attribute's worth of program: no raise
    assert read(types.SimpleNamespace(notes={})) is None
