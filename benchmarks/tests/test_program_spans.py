"""The per-layer readers that read what the program recorded about
itself (``harness/program_spans.py``), on a recorded span log: each
reader's arithmetic, the window's clipping, and None — never an
exception — where there is nothing to read."""

import importlib.util
import json
import os
import types

import pytest

from benchmarks.harness import program_spans as ps
from benchmarks.harness.cells import BENCH_DIR, load_manifest

PID = 4242
TRAINER, SERVER, MAIN = 11, 22, 33
OFFSET = 1000.0     # harness clock = telemetry clock + 1000 s


def _rec(name, ts, dur, tid=TRAINER, **attrs):
    rec = {"name": name, "ts": ts, "dur": dur, "pid": PID, "tid": tid,
           "role": "learner"}
    if attrs:
        rec["attrs"] = attrs
    return rec


def _recorded():
    """A window of 10 s, [100, 110) on the telemetry clock, with one
    epoch boundary inside it and one that straddles its close."""
    recs = []
    # before the window: priming on the main thread, an ingest that the
    # window cuts in half
    recs.append(_rec("trainer.ingest", 90.0, 1.0, tid=MAIN,
                     episodes=64))
    recs += [_rec("trainer.ingest", 99.98, 0.04, episodes=4),
             _rec("ingest.decompress", 99.98, 0.02),
             _rec("ingest.pad", 100.0, 0.01),
             _rec("ingest.append", 100.01, 0.01,
                  wait_ms=[40.0, 30.0, 20.0, 10.0])]
    # inside: 6 more episodes in two calls
    for k, t in enumerate((101.0, 102.0)):
        recs += [_rec("trainer.ingest", t, 0.03, episodes=3),
                 _rec("ingest.decompress", t, 0.012),
                 _rec("ingest.pad", t + 0.012, 0.009),
                 _rec("ingest.append", t + 0.021, 0.009,
                      wait_ms=[5.0 + k, 6.0 + k, 300.0 + k])]
    # 100 steps' dispatch of 20 ms each over [103, 105)
    recs += [_rec("trainer.update", 103.0 + 0.02 * i, 0.02)
             for i in range(100)]
    # a boundary inside the window
    recs += [_rec("trainer.boundary", 105.0, 0.4),
             _rec("boundary.drain", 105.0, 0.2),
             _rec("boundary.snapshot", 105.21, 0.05),
             _rec("boundary.checkpoint", 105.3, 0.1),
             _rec("trainer.handoff", 105.4, 0.1),
             _rec("learner.update", 104.9, 0.9, tid=SERVER)]
    # a boundary that closes after the window does: its time counts for
    # the thread's shares, it is no boundary "closed in the window"
    recs += [_rec("trainer.boundary", 109.8, 0.6),
             _rec("boundary.drain", 109.8, 0.5),
             _rec("learner.update", 109.7, 1.0, tid=SERVER)]
    # another process's spans never count
    recs.append(dict(_rec("trainer.update", 101.0, 5.0), pid=PID + 1))
    return recs


def _write(path, recs, pid=PID):
    with open(os.path.join(path, f"spans-{pid}.jsonl"), "w") as f:
        f.write(json.dumps({"meta": {"pid": pid, "role": "learner"}}) + "\n")
        for rec in recs:
            f.write(json.dumps(rec) + "\n")


def _run(log, profile=None):
    run = types.SimpleNamespace()
    run.notes = {}
    run.t_open, run.t_close = 100.0 + OFFSET, 110.0 + OFFSET
    run.window_s = 10.0
    run._program_spans = log
    trainer = types.SimpleNamespace(step_profile=lambda: profile)
    run.probes = types.SimpleNamespace(trainer=trainer)
    return run


@pytest.fixture()
def run(tmp_path):
    _write(tmp_path, _recorded())
    log = ps.read_log(str(tmp_path), OFFSET, 100.0 + OFFSET,
                      110.0 + OFFSET, pid=PID)
    return _run(log)


def test_ingest_parts_are_clipped_to_the_window_and_divided_by_its_episodes(
        run):
    # episodes of the ingest spans that END in the window, on the trainer
    # thread: 4 + 3 + 3 (the main thread's priming does not count)
    assert ps.ingest_decompress_ms_per_episode(run) == pytest.approx(
        1e3 * (0.0 + 2 * 0.012) / 10)       # the cut call's unzip lay before
    assert ps.ingest_pad_ms_per_episode(run) == pytest.approx(
        1e3 * (0.01 + 2 * 0.009) / 10)
    assert ps.ingest_append_ms_per_episode(run) == pytest.approx(
        1e3 * (0.01 + 2 * 0.009) / 10)


def test_queue_wait_is_the_p95_of_the_windows_appends(run):
    # 10 waits: 40 30 20 10 5 6 300 6 7 301 -> nearest rank 95% = 301
    assert ps.ring_queue_wait_p95_ms(run) == 301.0


def test_boundary_parts_are_means_over_boundaries_closed_in_the_window(run):
    assert ps.boundary_drain_ms(run) == pytest.approx(200.0)
    assert ps.boundary_snapshot_ms(run) == pytest.approx(50.0)
    assert ps.boundary_checkpoint_ms(run) == pytest.approx(100.0)
    assert ps.server_update_ms(run) == pytest.approx(900.0)


def test_thread_shares_and_the_untracked_residual(run):
    assert ps.dispatch_thread_share(run) == pytest.approx(20.0)
    # ingest 0.02 + 0.06, update 2.0, boundary 0.4 + 0.2 (clipped),
    # handoff 0.1 of 10 s
    assert ps.trainer_untracked_share(run) == pytest.approx(
        100.0 * (1 - (0.08 + 2.0 + 0.6 + 0.1) / 10.0))


def test_step_phases_come_from_one_cached_capture():
    calls = []
    profile = {"steps": 16, "step_ms": 4.6, "unmatched_ms": 0.0,
               "phases": {"gather": 1.0, "forward": 1.2, "targets": 0.1,
                          "backward": 2.0, "optimizer": 0.2,
                          "unscoped": 0.1}}
    run = _run(None, profile)
    run.probes.trainer.step_profile = lambda: calls.append(1) or profile
    got = [reader(run) for reader in (
        ps.step_gather_ms, ps.step_forward_ms, ps.step_targets_ms,
        ps.step_backward_ms, ps.step_optimizer_ms, ps.step_unscoped_ms)]
    assert got == [1.0, 1.2, 0.1, 2.0, 0.2, 0.1]
    assert sum(got) == pytest.approx(profile["step_ms"])
    assert "step_profile_seconds" in run.notes and "16 steps" in \
        run.notes["step_profile"]
    assert len(calls) == 6      # the program caches; the reader just asks


READERS = [m["name"] for m in load_manifest()["per_layer"]
           if os.path.exists(os.path.join(
               BENCH_DIR, "layer_metrics", m["name"] + ".py"))
           and "program_spans" in open(os.path.join(
               BENCH_DIR, "layer_metrics", m["name"] + ".py")).read()]


def _read(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name, os.path.join(
            BENCH_DIR, "layer_metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_manifest_lists_every_reader_of_the_programs_own_record():
    """The count follows the files: every reader file has its entry
    and every entry its file (``READERS`` are those of them that read
    ``program_spans``)."""
    manifest = load_manifest()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    files = {f[:-3] for f in os.listdir(os.path.join(
        BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert files == set(entries)        # episode_to_ring_p95_ms among them
    assert len(READERS) >= 17
    assert entries["ring_queue_wait_p95_ms"]["workloads"] == ["geister.fed"]
    # the typical episode's wait is judged, the tail only read (PR 45)
    ends = {m["name"]: m for m in manifest["end_to_end"]}
    assert set(ends) == {"learner_frames_per_s", "episode_to_ring_p50_ms",
                         "setup_s"}
    assert ends["episode_to_ring_p50_ms"]["workloads"] == ["geister.fed"]
    moves = {"episode_to_ring_p95_ms": "episode_to_ring_p50_ms",
             "ring_queue_wait_p95_ms": "episode_to_ring_p50_ms",
             "server_update_ms": "learner_frames_per_s"}
    for name, moved in moves.items():
        assert entries[name]["moves"] == moved
        assert entries[name]["workloads"] == ["geister.fed"]
    assert {entries[n]["source"] for n in READERS} == {
        "program_span", "device_trace", "program_counter"}


@pytest.mark.parametrize("name", READERS)
def test_every_reader_finds_its_number_on_the_recorded_log(name, run):
    run.probes.trainer.step_profile = lambda: {
        "steps": 2, "step_ms": 6.0, "phases": dict.fromkeys(
            ("gather", "forward", "targets", "backward", "optimizer",
             "unscoped"), 1.0),
        "counters": {"held_pick_share": 0.125}}
    value = _read(name)(run)
    assert isinstance(value, float) and value > 0, name


@pytest.mark.parametrize("name", READERS)
def test_no_reader_raises_where_there_is_nothing_to_read(name, tmp_path):
    read = _read(name)
    # no log at all (telemetry off, or the file missing)
    assert read(_run(None)) is None
    # a log that holds none of the new spans: the parent's program
    _write(tmp_path, [_rec("trainer.update", 103.0, 0.02),
                      _rec("trainer.ingest", 101.0, 0.01)])
    old = ps.read_log(str(tmp_path), OFFSET, 100.0 + OFFSET,
                      110.0 + OFFSET, pid=PID)
    value = read(_run(old))
    assert value is None or name == "dispatch_thread_share"
    # a program without step_profile at all
    bare = _run(None)
    bare.probes.trainer = types.SimpleNamespace()
    assert read(bare) is None
    # a window that holds nothing
    empty = _run(ps.read_log(str(tmp_path), OFFSET, 500.0, 510.0, pid=PID))
    empty.t_open, empty.t_close = 500.0, 510.0
    assert read(empty) in (None, 0.0)


def test_load_reads_the_live_programs_log_once_and_maps_the_clock(
        tmp_path, monkeypatch):
    import time

    from handyrl_tpu import telemetry

    monkeypatch.chdir(tmp_path)
    telemetry.configure(enabled=True, log_dir=".", role="learner")
    try:
        t_open = time.perf_counter()
        with telemetry.trace_span("trainer.update"):
            time.sleep(0.02)
        with telemetry.trace_span("trainer.handoff"):
            pass
        run = _run(None)
        del run._program_spans
        run.t_open, run.t_close = t_open, time.perf_counter()
        run.window_s = run.t_close - run.t_open
        run.probes.learner = types.SimpleNamespace(
            args={"metrics_path": "metrics.jsonl"})
        share = ps.dispatch_thread_share(run)    # flushes, reads, maps
        assert 0.5 * 0.02 / run.window_s <= share / 100.0 <= 1.0
        (span,) = ps.load(run).spans("trainer.update")
        assert t_open <= span[0] < span[1] <= run.t_close
        assert ps.load(run) is run._program_spans
        telemetry.configure(enabled=False)
        off = _run(None)
        del off._program_spans
        off.probes.learner = run.probes.learner
        assert ps.load(off) is None and ps.server_update_ms(off) is None
    finally:
        telemetry.configure(enabled=False)
