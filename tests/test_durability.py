"""Durability layer: checksummed checkpoints, manifest fallback, the
episode WAL, the learner kill switch, and the relaunch guard.

The unit half proves each corruption mode is REJECTED (truncated,
bit-flipped, zero-length files), that fallback ordering walks the
manifest newest-valid-first, and that WAL replay is idempotent.  The
e2e half is the acceptance proof for the whole layer: a hard SIGKILL
of the learner process mid-epoch, auto-resume from the manifest with
exact optimizer state, and the WAL-restored backlog — deliberately in
tier-1 (deterministic: the kill is scheduled on the intake clock, the
guard's backoff is pinned, and resume is a pure function of the files
on disk)."""

import json
import os
import pickle
import threading

import numpy as np
import pytest

from handyrl_tpu.durability import (
    CheckpointManifest,
    CorruptCheckpointError,
    EpisodeWAL,
    read_verified,
    resolve_restart,
    verify_file,
    write_checksummed,
)
from handyrl_tpu.resilience import BackoffPolicy, ChaosConfig
from handyrl_tpu.resilience.chaos import LearnerKillSwitch
from handyrl_tpu.resilience.guardian import LearnerGuard


# -- checksummed checkpoint files ----------------------------------------

def test_checksum_roundtrip_and_legacy_load(tmp_path):
    path = str(tmp_path / "a.ckpt")
    digest = write_checksummed(path, {"epoch": 3, "params": [1.5, 2.5]})
    assert len(digest) == 64
    assert read_verified(path)["epoch"] == 3
    assert read_verified(path, expect_digest=digest)["epoch"] == 3
    # a plain pickle.load still works: the footer trails the stream
    with open(path, "rb") as f:
        assert pickle.load(f)["epoch"] == 3
    # legacy footer-less files load (verified by unpickling only)
    legacy = str(tmp_path / "legacy.ckpt")
    with open(legacy, "wb") as f:
        pickle.dump({"epoch": 7}, f)
    assert read_verified(legacy)["epoch"] == 7


@pytest.mark.parametrize("corruption", ["truncated", "bitflip", "empty"])
def test_corrupt_checkpoints_are_rejected(tmp_path, corruption):
    path = str(tmp_path / "a.ckpt")
    write_checksummed(path, {"epoch": 1, "params": list(range(100))})
    data = open(path, "rb").read()
    if corruption == "truncated":
        open(path, "wb").write(data[: len(data) // 2])
    elif corruption == "bitflip":
        flip = bytearray(data)
        flip[len(flip) // 3] ^= 0x40
        open(path, "wb").write(bytes(flip))
    else:
        open(path, "wb").close()
    with pytest.raises(CorruptCheckpointError):
        read_verified(path)
    assert not verify_file(path)


def test_wrong_manifest_digest_is_rejected(tmp_path):
    path = str(tmp_path / "a.ckpt")
    write_checksummed(path, {"epoch": 1})
    with pytest.raises(CorruptCheckpointError):
        read_verified(path, expect_digest="0" * 64)
    assert not verify_file(path, expect_digest="0" * 64)


# -- manifest + resume resolution ----------------------------------------

def _commit_epoch(tmp_path, manifest, epoch, steps=None):
    path = str(tmp_path / f"{epoch}.ckpt")
    digest = write_checksummed(
        path, {"epoch": epoch, "steps": steps or epoch * 10,
               "params": {"w": [float(epoch)]}})
    manifest.commit(epoch, path, digest, steps or epoch * 10)
    return path


def test_manifest_fallback_ordering(tmp_path):
    manifest = CheckpointManifest(str(tmp_path))
    paths = {e: _commit_epoch(tmp_path, manifest, e) for e in (1, 2, 3)}
    assert manifest.newest_valid()[0] == 3
    # corrupt the newest: fallback walks to the next valid entry
    open(paths[3], "wb").write(b"\x00" * 10)
    assert manifest.newest_valid()[0] == 2
    open(paths[2], "wb").close()  # zero-length
    assert manifest.newest_valid()[0] == 1
    assert manifest.newest_valid(below=1) is None
    # transactional writes never leave a tmp file behind
    assert not os.path.exists(manifest.path + ".tmp")


def test_manifest_forget_drops_pruned_epochs(tmp_path):
    manifest = CheckpointManifest(str(tmp_path))
    for e in (1, 2, 3):
        _commit_epoch(tmp_path, manifest, e)
    manifest.forget([1, 2])
    assert sorted(manifest.load()["entries"]) == ["3"]


def test_resolve_restart_auto_prefers_manifest_latest(tmp_path):
    assert resolve_restart(str(tmp_path), "auto").epoch == 0  # no files
    assert resolve_restart(str(tmp_path), 0).source == "fresh"
    manifest = CheckpointManifest(str(tmp_path))
    for e in (1, 2):
        _commit_epoch(tmp_path, manifest, e)
    point = resolve_restart(str(tmp_path), "auto")
    assert point.epoch == 2 and point.source == "manifest"


def test_resolve_restart_corrupt_latest_falls_back(tmp_path):
    """The acceptance criterion's corrupted-latest variant at the
    resolution layer: a truncated newest checkpoint resumes from the
    previous valid epoch instead of crashing."""
    manifest = CheckpointManifest(str(tmp_path))
    for e in (1, 2, 3):
        path = _commit_epoch(tmp_path, manifest, e)
    data = open(path, "rb").read()
    open(path, "wb").write(data[:20])  # truncate epoch 3
    point = resolve_restart(str(tmp_path), "auto")
    assert point.epoch == 2
    # explicit request for the corrupt epoch falls back too, loudly
    point = resolve_restart(str(tmp_path), 3)
    assert point.epoch == 2 and point.source == "fallback"
    # an unsatisfiable explicit request fails instead of silently
    # training from scratch
    for e in (1, 2):
        open(str(tmp_path / f"{e}.ckpt"), "wb").close()
    with pytest.raises(CorruptCheckpointError):
        resolve_restart(str(tmp_path), 3)


def test_resolve_restart_survives_lost_manifest(tmp_path):
    write_checksummed(str(tmp_path / "latest.ckpt"),
                      {"epoch": 4, "params": {}})
    point = resolve_restart(str(tmp_path), "auto")
    assert point.epoch == 4 and point.source == "latest"


# -- episode WAL ---------------------------------------------------------

def _fill_wal(tmp_path, counts=(4, 3), **kw):
    wal = EpisodeWAL(str(tmp_path / "wal"), flush_interval=0, **kw)
    i = 0
    for n in counts:
        for _ in range(n):
            wal.append({"i": i})
            i += 1
        wal.roll()
    return wal


def test_wal_roundtrip_and_double_replay_is_idempotent(tmp_path):
    wal = _fill_wal(tmp_path)
    seen = set()
    first = [ep["i"] for _, ep in wal.replay(seen)]
    assert first == list(range(7))
    # double replay of the SAME sealed segments admits nothing twice
    assert [ep for _, ep in wal.replay(seen)] == []
    # a fresh incarnation (new seen set) replays everything once more
    wal2 = EpisodeWAL(str(tmp_path / "wal"), flush_interval=0)
    assert wal2.seq == 7 and wal2.episode_count() == 7
    assert [ep["i"] for _, ep in wal2.replay(set())] == list(range(7))


def test_wal_torn_tail_stops_that_segment_only(tmp_path):
    wal = _fill_wal(tmp_path, counts=(3, 3))
    segs = wal.segments()
    data = open(segs[0], "rb").read()
    open(segs[0], "wb").write(data[:-5])  # crash tail: torn record
    got = [ep["i"] for _, ep in wal.replay(set())]
    # segment 0 loses its last record; segment 1 replays in full
    assert got == [0, 1, 3, 4, 5]


def test_wal_bitflip_drops_segment_remainder(tmp_path):
    wal = _fill_wal(tmp_path, counts=(3, 2))
    segs = wal.segments()
    data = bytearray(open(segs[0], "rb").read())
    data[len(data) // 2] ^= 0x01  # flip a bit in a middle record
    open(segs[0], "wb").write(bytes(data))
    got = [ep["i"] for _, ep in wal.replay(set())]
    assert got[-2:] == [3, 4]          # the next segment is intact
    assert len(got) < 5                # something was rejected


def test_wal_zero_length_segment_is_harmless(tmp_path):
    wal = _fill_wal(tmp_path, counts=(2,))
    open(os.path.join(str(tmp_path / "wal"), "seg-000099.wal"),
         "wb").close()
    assert [ep["i"] for _, ep in wal.replay(set())] == [0, 1]
    # and a fresh open scans past it without crashing
    wal2 = EpisodeWAL(str(tmp_path / "wal"), flush_interval=0)
    assert wal2.episode_count() == 2


def test_wal_retirement_keeps_buffer_coverage(tmp_path):
    wal = _fill_wal(tmp_path, counts=(4, 4, 4))
    # newer segments must cover keep_episodes before anything retires
    assert wal.retire(9) == []
    removed = wal.retire(8)
    assert len(removed) == 1 and wal.episode_count() == 8
    assert wal.retire(100) == []


def test_wal_flush_cadence_with_injected_clock(tmp_path):
    now = [0.0]
    wal = EpisodeWAL(str(tmp_path / "wal"), flush_interval=5.0,
                     clock=lambda: now[0])
    wal.append({"i": 0})
    flushed_at_start = wal.flushes
    wal.append({"i": 1})
    assert wal.flushes == flushed_at_start  # inside the cadence window
    now[0] += 6.0
    assert wal.maybe_flush() is True
    assert wal.maybe_flush() is False  # nothing dirty


# -- chaos kill switch + relaunch guard ----------------------------------

def test_kill_switch_fires_mid_window_once_per_run_dir(tmp_path):
    fired = []
    cfg = ChaosConfig.from_config(
        {"learner_kill_epoch": 2, "learner_kill_after_episodes": 3})
    marker = str(tmp_path / "models" / "killed")
    switch = LearnerKillSwitch(cfg, marker, kill=lambda: fired.append(1))
    assert not switch.note(1, 50)        # epoch not reached
    assert not switch.note(2, 50)        # arms: kill at 53
    assert not switch.note(2, 52)
    assert switch.note(2, 53)
    assert fired == [1] and os.path.exists(marker)
    # a relaunched incarnation (same run dir) must NOT be re-killed
    relaunch = LearnerKillSwitch(cfg, marker,
                                 kill=lambda: fired.append(2))
    assert not relaunch.armed
    assert not relaunch.note(2, 999)
    assert fired == [1]


class _FakeProc:
    def __init__(self, code):
        self.exitcode = code

    def join(self):
        pass


def test_learner_guard_relaunches_with_auto_resume():
    codes = [-9, 1, 0]  # SIGKILL, crash, clean finish
    spawned = []

    def spawn(target, args):
        spawned.append(args)
        return _FakeProc(codes.pop(0))

    guard = LearnerGuard(
        None, {"train_args": {"restart_epoch": 0}}, max_restarts=5,
        policy=BackoffPolicy(base=0.01, jitter=0.0),
        spawn=spawn, sleep=lambda s: None)
    assert guard.run() == 0
    assert guard.restarts == 2 and not guard.tripped
    # the first launch keeps the operator's config; every relaunch
    # resumes from the manifest
    assert spawned[0]["train_args"]["restart_epoch"] == 0
    assert spawned[1]["train_args"]["restart_epoch"] == "auto"
    assert spawned[2]["train_args"]["restart_epoch"] == "auto"


def test_learner_guard_circuit_breaker_stops_restart_storm():
    launches = []

    def spawn(target, args):
        launches.append(1)
        return _FakeProc(17)  # poison checkpoint: dies every time

    guard = LearnerGuard(
        None, {"train_args": {}}, max_restarts=2, failure_window=600.0,
        policy=BackoffPolicy(base=0.01, jitter=0.0),
        spawn=spawn, clock=lambda: 100.0, sleep=lambda s: None)
    assert guard.run() == 17
    assert guard.tripped
    # max_restarts=2 allows 2 relaunches: 3 launches total, then trip
    assert len(launches) == 3


# -- e2e: SIGKILL the learner mid-epoch, auto-resume from the manifest ----

def _train_args(extra_train=None, epochs=3):
    train = {
        "turn_based_training": True,
        "observation": False,
        "gamma": 0.8,
        "forward_steps": 4,
        "burn_in_steps": 0,
        "compress_steps": 4,
        "entropy_regularization": 0.1,
        "entropy_regularization_decay": 0.1,
        "update_episodes": 12,
        "batch_size": 4,
        "minimum_episodes": 10,
        "maximum_episodes": 200,
        "epochs": epochs,
        "num_batchers": 1,
        "eval_rate": 0.1,
        "worker": {"num_parallel": 2},
        "lambda": 0.7,
        "policy_target": "VTRACE",
        "value_target": "VTRACE",
        "seed": 1,
        "metrics_path": "metrics.jsonl",
    }
    train.update(extra_train or {})
    return {
        "env_args": {"env": "TicTacToe"},
        "train_args": train,
        "worker_args": {"num_parallel": 2, "server_address": ""},
    }


def _killable_train(args):
    """Supervised-child entry: pin jax to CPU FIRST (a spawned child
    must choose the CPU before its first JAX call), then run one
    learner."""
    from handyrl_tpu.connection import force_cpu_jax

    force_cpu_jax()
    from handyrl_tpu.learner import _train_local

    _train_local(args)


def test_learner_sigkill_auto_resume_completes_training(
        tmp_path, monkeypatch):
    """The durability acceptance proof, end to end: the chaos kill
    switch SIGKILLs the learner process mid-epoch (4 episodes into
    epoch 2's window — between checkpoints, with a staged backlog only
    the WAL remembers), the LearnerGuard relaunches it with
    ``restart_epoch: auto``, and the resumed learner (a) finds the
    newest valid manifest entry without config surgery, (b) restores
    optimizer state EXACTLY (leaf-wise vs train_state.ckpt, asserted
    on a fresh in-process resume below), (c) replays the WAL backlog
    (``episodes_replayed > 0`` in metrics.jsonl), and (d) completes
    every configured epoch.

    Deliberately in tier-1 (~60s): the kill is scheduled on the intake
    clock (not timing), the guard's backoff is pinned jitter-free, and
    resume is a pure function of the files on disk."""
    monkeypatch.chdir(tmp_path)

    args = _train_args(extra_train={
        "wal_flush_interval": 0.1,
        "chaos": {"learner_kill_epoch": 2,
                  "learner_kill_after_episodes": 4, "seed": 7},
    }, epochs=3)

    guard = LearnerGuard(
        _killable_train, args, max_restarts=2,
        policy=BackoffPolicy(base=0.2, jitter=0.0))
    assert guard.run() == 0

    # the kill fired (marker fsync'd before the SIGKILL) and exactly
    # one relaunch finished the job
    assert os.path.exists("models/chaos_learner_killed")
    assert guard.restarts == 1 and not guard.tripped

    # every epoch completed across the two incarnations, numbering
    # continuous (epoch stamped at epoch start: [0, 1] + resumed [2])
    with open("metrics.jsonl") as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert [r["epoch"] for r in records] == [0, 1, 2]
    # the resumed incarnation re-entered a WARM pipeline: the WAL
    # restored the backlog instead of re-generating it.  The bound is
    # the episode-loss window: everything admitted before the kill
    # (~38 episodes) minus at most the unsynced tail
    assert records[-1]["episodes_replayed"] >= 20
    assert records[0]["episodes_replayed"] == 0
    assert all("wal_appended" in r for r in records)
    assert os.path.exists("models/3.ckpt")

    # the manifest indexes the finished run and its files verify
    manifest = CheckpointManifest("models")
    latest = manifest.load()["latest"]
    assert latest["epoch"] == 3 and not latest["emergency"]
    assert verify_file("models/3.ckpt", latest["digest"])

    # (b) EXACT optimizer-state restore: a fresh auto-resume restores
    # steps + every optimizer leaf bit-identical to train_state.ckpt
    saved = read_verified("models/train_state.ckpt")
    assert saved["epoch"] == 3 and saved["steps"] > 0
    from handyrl_tpu.learner import Learner

    args2 = _train_args(epochs=4)
    args2["train_args"]["restart_epoch"] = "auto"
    learner = Learner(args2)
    try:
        assert learner.model_epoch == 3
        assert learner.trainer.steps == saved["steps"]
        import jax

        restored = [np.asarray(x) for x in
                    jax.tree.leaves(learner.trainer.opt_state)]
        expected = [np.asarray(x) for x in
                    jax.tree.leaves(saved["opt_state"])]
        assert len(restored) == len(expected) > 0
        for got, want in zip(restored, expected):
            assert np.array_equal(got, want)
        # (c) again, observable in-process: the backlog came back
        assert learner.episodes_replayed >= 20

        # emergency-save drill (the SIGTERM grace-window path, driven
        # directly — no signal needed): the trainer lands a consistent
        # latest.ckpt + train state and the manifest re-points at it
        event = threading.Event()
        learner.trainer.emergency = event
        learner.trainer._maybe_emergency_save()
        assert event.is_set()
        point = resolve_restart("models", "auto")
        assert point.source == "emergency"
        assert point.epoch == 3
        emergency = read_verified("models/latest.ckpt")
        assert emergency["steps"] == saved["steps"]
    finally:
        if learner.stall_watchdog is not None:
            learner.stall_watchdog.stop()
        if learner.wal is not None:
            learner.wal.close()


def test_learner_corrupted_latest_falls_back_one_epoch(
        tmp_path, monkeypatch):
    """Learner-level corrupted-latest variant: checkpoints for epochs
    1 and 2 exist, epoch 2's file is truncated — auto-resume comes up
    at epoch 1 with epoch 1's params instead of crashing (or training
    on garbage)."""
    monkeypatch.chdir(tmp_path)
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel

    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=1)
    import jax

    params1 = jax.tree.map(np.asarray, model.params)

    os.makedirs("models", exist_ok=True)
    manifest = CheckpointManifest("models")

    for epoch in (1, 2):
        scaled = jax.tree.map(lambda a, e=epoch: np.asarray(a) * e,
                              params1)
        digest = write_checksummed(
            f"models/{epoch}.ckpt",
            {"params": scaled, "steps": epoch * 5, "epoch": epoch})
        manifest.commit(epoch, f"models/{epoch}.ckpt", digest,
                        epoch * 5)
    data = open("models/2.ckpt", "rb").read()
    open("models/2.ckpt", "wb").write(data[: len(data) // 2])

    from handyrl_tpu.learner import Learner

    args = _train_args()
    args["train_args"]["restart_epoch"] = "auto"
    learner = Learner(args)
    try:
        assert learner.model_epoch == 1
        want = jax.tree.leaves(params1)
        got = jax.tree.leaves(learner.model.params)
        assert len(got) == len(want) > 0
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g), np.asarray(w))
    finally:
        if learner.stall_watchdog is not None:
            learner.stall_watchdog.stop()
        if learner.wal is not None:
            learner.wal.close()


def test_learner_guard_failures_age_out_of_window():
    codes = [1, 1, 0]
    times = iter([0.0, 1000.0, 2000.0])

    def spawn(target, args):
        return _FakeProc(codes.pop(0))

    guard = LearnerGuard(
        None, {"train_args": {}}, max_restarts=1, failure_window=60.0,
        policy=BackoffPolicy(base=0.01, jitter=0.0),
        spawn=spawn, clock=lambda: next(times), sleep=lambda s: None)
    # two failures, but 1000s apart: each window holds one -> no trip
    assert guard.run() == 0
    assert not guard.tripped and guard.restarts == 2
