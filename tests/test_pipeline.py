"""Pipelined rollout dataflow: shm transport + batched inference service.

Three layers, matching the subsystem's own:

  * ring units — wraparound, full-ring backpressure, torn-write
    detection, reader-crash reclaim: the seqlock transport's whole
    failure contract, no processes needed (cursors live in the
    segment, so both endpoints can be mapped in one test process);
  * service units — the wait-or-timeout batching window under an
    INJECTED clock (a scripted sleep delivers the second worker's
    request mid-window), hot-swap, epoch pinning, fallback/respawn;
  * one deterministic tier-1 e2e — a real training run with the
    pipeline on whose inference service is chaos-killed mid-train
    (``chaos.infer_kill_epoch``): training must complete via the
    workers' local fallback plus the learner's supervised respawn.
"""

import json

import numpy as np
import pytest

from handyrl_tpu.pipeline import (
    PipelineClient,
    PipelineConfig,
    ShmBoard,
    ShmRing,
)
from handyrl_tpu.pipeline import shm as shm_mod


# ---------------------------------------------------------------------
# ring units
# ---------------------------------------------------------------------

def test_ring_wraparound_fifo():
    """20 items through 4 slots: FIFO order survives five laps."""
    ring = ShmRing.create(slots=4, slot_bytes=64)
    try:
        for i in range(20):
            assert ring.push(f"item-{i}".encode())
            assert ring.pop() == f"item-{i}".encode()
        assert ring.pop() is None  # drained
    finally:
        ring.close()


def test_ring_full_backpressure_counts():
    """A full ring refuses pushes (never overwrites) and counts the
    refusal in the shm header where the CONSUMER side can read it."""
    ring = ShmRing.create(slots=3, slot_bytes=64)
    try:
        for i in range(3):
            assert ring.push(b"x")
        assert len(ring) == 3
        assert not ring.push(b"overflow")
        assert ring.full_count == 1
        assert ring.pop() == b"x"   # drain one slot...
        assert ring.push(b"y")      # ...and the producer flows again
        assert ring.full_count == 1
    finally:
        ring.close()


def test_ring_oversize_item_refused():
    """An item larger than one slot is refused and counted — the
    producer's cue to spill to the control plane."""
    ring = ShmRing.create(slots=2, slot_bytes=16)
    try:
        assert not ring.push(b"z" * 17)
        assert ring.full_count == 1 and len(ring) == 0
        assert ring.push(b"z" * 16)  # exactly one slot fits
    finally:
        ring.close()


def _tear_slot(ring):
    """Simulate a producer dying mid-write: reserve the slot (odd
    seqlock stamp + head bump — exactly what push() publishes first)
    and never fill it."""
    head = ring._get(shm_mod._HEAD)
    off = ring._slot_off(head)
    shm_mod._Q.pack_into(ring._buf, off, 2 * head + 1)
    ring._set(shm_mod._HEAD, head + 1)


def test_ring_torn_write_detected_and_skipped():
    """A slot whose writer died mid-frame is never consumed as data;
    once the consumer has evidence the writer is gone, skip_torn
    reclaims the ring and later traffic flows."""
    ring = ShmRing.create(slots=4, slot_bytes=64)
    try:
        assert ring.push(b"good-1")
        _tear_slot(ring)
        assert ring.pop() == b"good-1"
        # the torn slot: pending but never readable
        assert ring.pending() and not ring.readable()
        assert ring.pop() is None
        # reclaim (the caller decided the writer is dead)
        assert ring.skip_torn()
        assert ring.torn_count == 1
        assert not ring.skip_torn()  # nothing torn anymore
        # the ring flows again past the reclaimed slot
        assert ring.push(b"good-2")
        assert ring.pop() == b"good-2"
    finally:
        ring.close()


def test_ring_reader_crash_reclaim():
    """All consumer state (tail cursor) lives in the segment: a
    successor attaching by name resumes exactly where the crashed
    reader stopped — nothing buffered in a lost process heap."""
    ring = ShmRing.create(slots=8, slot_bytes=64)
    try:
        for i in range(5):
            assert ring.push(f"m{i}".encode())
        reader1 = ShmRing.attach(**ring.descriptor())
        assert reader1.pop() == b"m0"
        assert reader1.pop() == b"m1"
        reader1.close()  # the "crash": the mapping goes away, cursors stay

        reader2 = ShmRing.attach(**ring.descriptor())
        assert reader2.pop() == b"m2"  # resumes, no loss, no replay
        assert len(reader2) == 2
        reader2.close()
    finally:
        ring.close()


def test_board_beat_age_epoch_generation():
    board = ShmBoard.create()
    try:
        assert board.age() == float("inf")  # never beaten
        board.beat(epoch=7, now=100.0)
        peer = ShmBoard.attach(board.name)
        assert peer.epoch == 7
        assert peer.age(now=100.5) == pytest.approx(0.5)
        board.bump_generation()
        assert peer.generation == 1
        peer.close()
    finally:
        board.close()


def test_request_codec_roundtrip():
    """The raw obs frame codec: leaves in, identical leaves out, laid
    out by the attach-time schema (no pickle on the hot path)."""
    leaves = [np.arange(12, dtype=np.float32).reshape(2, 6),
              np.array([[1], [0]], dtype=np.int32)]
    specs = [((6,), "float32"), ((1,), "int32")]
    parts = shm_mod.pack_request(3, 2, leaves)
    blob = b"".join(bytes(p) for p in parts)
    seq, rows, out = shm_mod.unpack_request(memoryview(blob), specs)
    assert (seq, rows) == (3, 2)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------

def test_pipeline_config_defaults_on_and_validates():
    # the pipelined dataflow IS the mainline: an empty section runs
    # with the shm transport armed (remote workers and recurrent nets
    # auto-fall-back); `mode: off` restores the legacy path wholesale
    assert PipelineConfig.from_config({}).enabled
    assert PipelineConfig.from_config(None).enabled
    assert not PipelineConfig.from_config({"mode": "off"}).enabled
    assert PipelineConfig.from_config({"mode": "on"}).enabled
    with pytest.raises(ValueError, match="unknown pipeline keys"):
        PipelineConfig.from_config({"bogus": 1})
    with pytest.raises(ValueError, match="pipeline.mode"):
        PipelineConfig.from_config({"mode": "sideways"})
    with pytest.raises(ValueError, match="fallback"):
        PipelineConfig.from_config({"fallback": "explode"})
    with pytest.raises(ValueError, match="ring_slots"):
        PipelineConfig.from_config({"ring_slots": 0})
    with pytest.raises(ValueError, match="fallback_after"):
        PipelineConfig.from_config({"fallback_after": 0})


def test_train_config_validates_pipeline_section():
    from handyrl_tpu.config import Config

    raw = {"env_args": {"env": "TicTacToe"},
           "train_args": {"pipeline": {"mode": "on",
                                       "batch_window": 0.01}}}
    cfg = Config.from_dict(raw)
    assert cfg.train_args["pipeline"]["mode"] == "on"
    raw["train_args"]["pipeline"] = {"made_up": True}
    with pytest.raises(ValueError, match="unknown pipeline keys"):
        Config.from_dict(raw)


def test_chaos_infer_kill_epoch_validates():
    from handyrl_tpu.resilience import ChaosConfig

    cfg = ChaosConfig.from_config({"infer_kill_epoch": 2})
    assert cfg.infer_kill_enabled
    assert not ChaosConfig.from_config({}).infer_kill_enabled
    with pytest.raises(ValueError):
        ChaosConfig.from_config({"infer_kill_epoch": -1})


# ---------------------------------------------------------------------
# episode wire formats
# ---------------------------------------------------------------------

def test_raw_and_bz2_episode_blocks_are_interchangeable():
    """pack_episode(compress=False) produces raw pickle blocks that
    every consumer (batch maker, device-replay ingest) decodes
    identically to the legacy bz2 format — the two mix freely in one
    replay buffer (blocks are magic-sniffed)."""
    import random

    from handyrl_tpu.batch import decompress_moments
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import Generator
    from handyrl_tpu.models import RandomModel, TPUModel
    from handyrl_tpu.staging import _decompress_episode

    random.seed(0)
    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    obs0 = env.observation(env.players()[0])
    model.init_params(obs0, seed=0)
    rollout = RandomModel(model, obs0)
    players = env.players()
    job = {"player": players, "model_id": {p: 0 for p in players}}

    cfg = {"turn_based_training": True, "observation": False,
           "gamma": 0.8, "compress_steps": 4}
    raw_ep = None
    while raw_ep is None:
        raw_ep = Generator(env, dict(cfg, episode_compress=False)
                           ).generate({p: rollout for p in players}, job)
    assert all(b[:2] != b"BZ" for b in raw_ep["moment"])

    # re-pack the SAME moments compressed, decode both ways
    from handyrl_tpu.generation import pack_episode

    moments = decompress_moments(
        {**raw_ep, "start": 0, "end": raw_ep["steps"], "base": 0})
    bz_ep = pack_episode(moments, raw_ep["outcome"], raw_ep["args"], 4,
                         compress=True)
    assert all(b[:2] == b"BZ" for b in bz_ep["moment"])

    a = _decompress_episode(raw_ep)
    b = _decompress_episode(bz_ep)
    np.testing.assert_array_equal(a["prob"], b["prob"])
    np.testing.assert_array_equal(a["act"], b["act"])
    for la, lb in zip(np.asarray(a["obs"]).ravel(),
                      np.asarray(b["obs"]).ravel()):
        assert la == lb


# ---------------------------------------------------------------------
# batching-window units (injected clock)
# ---------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.now = 0.0
        self.on_advance = None  # callable(now) hook (scripted arrivals)

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += dt
        if self.on_advance is not None:
            self.on_advance(self.now)


class _StubModel:
    """Counts forwards; policy = row index so replies are checkable."""

    module = "stub"

    def __init__(self):
        self.calls = []

    def inference_batch(self, obs, hidden=None):
        rows = obs.shape[0]
        self.calls.append(rows)
        return {"policy": np.tile(
            np.arange(rows, dtype=np.float32)[:, None], (1, 3))}


def _make_service(window=1.0, max_batch=64):
    from handyrl_tpu.pipeline.service import InferenceService

    cfg = PipelineConfig.from_config({
        "mode": "on", "batch_window": window, "max_batch": max_batch,
        "ring_slots": 8, "slot_bytes": 4096,
        "traj_slots": 4, "traj_slot_mb": 1})
    clock = _FakeClock()
    model = _StubModel()
    svc = InferenceService(model, cfg, epoch=1,
                           clock=clock, sleep=clock.sleep)
    return svc, clock, model


def _push_request(svc, desc, seq, rows):
    req = ShmRing.attach(**desc["req"])
    leaves = [np.full((rows, 2), float(seq), np.float32)]
    assert req.push(shm_mod.pack_request(seq, rows, leaves))
    req.close()


def _pop_reply(desc):
    rsp = ShmRing.attach(**desc["rsp"])
    out = rsp.pop(loads=shm_mod.loads_view)
    rsp.close()
    return out


def test_batching_window_waits_for_batch_mates():
    """The wait-or-timeout window: a second worker's request arriving
    mid-window joins the SAME dispatch; the wait is accounted into
    infer_queue_wait_sec."""
    svc, clock, model = _make_service(window=1.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        d1 = svc.attach(spec)
        d2 = svc.attach(spec)
        _push_request(svc, d1, seq=1, rows=2)

        # scripted arrival: worker 2's request lands 0.4s into the window
        def arrive(now):
            if now >= 0.4 and not arrive.done:
                arrive.done = True
                _push_request(svc, d2, seq=1, rows=3)
        arrive.done = False
        clock.on_advance = arrive

        assert svc.step()
        assert model.calls == [8]          # 5 rows bucket-padded to 8
        r1 = _pop_reply(d1)
        r2 = _pop_reply(d2)
        assert r1[0] == 1 and r2[0] == 1   # both answered, matching seq
        assert r1[2]["policy"].shape == (2, 3)
        assert r2[2]["policy"].shape == (3, 3)
        # rows sliced in arrival order: d1 rows 0-1, d2 rows 2-4
        np.testing.assert_array_equal(r1[2]["policy"][:, 0], [0, 1])
        np.testing.assert_array_equal(r2[2]["policy"][:, 0], [2, 3, 4])
        stats = svc.epoch_stats()
        assert stats["infer_batches"] == 1
        assert stats["infer_requests"] == 2
        assert stats["infer_batch_size_mean"] == 5.0
        # dispatched at the window deadline: the wait is the window
        assert stats["infer_queue_wait_sec"] == pytest.approx(1.0,
                                                              abs=0.01)
    finally:
        svc.close()


def test_full_batch_short_circuits_the_window():
    """max_batch staged rows dispatch immediately — the window is a
    ceiling on latency, not a floor."""
    svc, clock, model = _make_service(window=5.0, max_batch=4)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        d1 = svc.attach(spec)
        _push_request(svc, d1, seq=1, rows=4)
        assert svc.step()
        assert clock.now < 5.0             # did not wait out the window
        assert model.calls == [4]          # no padding needed at cap
        assert svc.epoch_stats()["infer_batches"] == 1
    finally:
        svc.close()


def test_hot_swap_between_batches_answers_with_new_epoch():
    svc, clock, model = _make_service(window=0.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        d = svc.attach(spec)
        _push_request(svc, d, seq=1, rows=1)
        assert svc.step()
        assert _pop_reply(d)[1] == 1       # epoch 1 answered

        model2 = _StubModel()
        svc.set_model(model2, 2)           # the learner's hot swap
        _push_request(svc, d, seq=2, rows=1)
        assert svc.step()
        reply = _pop_reply(d)
        assert reply[1] == 2               # new snapshot, no drop
        assert model2.calls == [8]         # served BY the new model
    finally:
        svc.close()


# ---------------------------------------------------------------------
# served-model round trip + fallback/respawn (real service thread)
# ---------------------------------------------------------------------

def _real_service(mesh=None, fsdp=False, **cfg_over):
    import jax

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.pipeline import InferenceService, PipelineClient
    from handyrl_tpu.pipeline.client import build_obs_spec

    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(0), seed=0)
    cfg = PipelineConfig.from_config({
        "mode": "on", "batch_window": 0.001, "fallback_after": 0.4,
        **cfg_over})
    svc = InferenceService(model, cfg, epoch=1, mesh=mesh, fsdp=fsdp)
    svc.start()
    desc = svc.attach(build_obs_spec(env, 4))
    client = PipelineClient(desc, cfg)
    obs = env.observation(0)
    batch = jax.tree.map(lambda a: np.stack([np.asarray(a)] * 4), obs)
    return env, model, svc, client, obs, batch


def _wait_healthy(client, svc=None, timeout=10.0):
    """Wait for the first beat — and, when the service is given, for
    the attach-time jit warmup to finish, so the first served request
    is answered inside its reply deadline deterministically."""
    import time

    t0 = time.monotonic()
    while not client.healthy() or (svc is not None
                                   and svc.warm_pending):
        assert time.monotonic() - t0 < timeout, "service never warmed"
        time.sleep(0.01)


def test_served_inference_matches_local():
    """The served forward is bit-compatible with the local one (same
    params, same jit) across the batch, rows-selected, and single-obs
    entry points."""
    env, model, svc, client, obs, batch = _real_service()
    try:
        _wait_healthy(client, svc)
        served = client.wrap(model, epoch=1)
        local = model.inference_batch(batch, None)

        out = served.inference_batch(batch, None)
        np.testing.assert_allclose(out["policy"], local["policy"],
                                   rtol=1e-5)
        rows = np.array([0, 2])
        out = served.inference_batch(batch, None, rows=rows)
        np.testing.assert_allclose(out["policy"][rows],
                                   local["policy"][rows], rtol=1e-5)
        assert (out["policy"][1] == 0).all()  # unasked rows untouched

        single = served.inference(obs, None)
        np.testing.assert_allclose(
            single["policy"], model.inference(obs, None)["policy"],
            rtol=1e-5)
        assert svc.stats()["requests"] >= 3
        assert client.fallbacks == 0
    finally:
        svc.close()
        client.close()


def test_served_inference_on_multi_device_mesh():
    """served==local compatibility when the dispatch runs as ONE GSPMD
    program over the virtual 8-device mesh (dp4 x tp2 + fsdp): the
    real shm round trip answers within float32 epsilon of the local
    forward (row-sharded backend kernels reassociate — cross-PATH
    comparison is epsilon, not bitwise; the unsharded test above keeps
    the bitwise contract), the dispatch itself is deterministic
    (repeat requests bit-match each other), the snapshot was placed
    onto the param shardings exactly once, and the sharding-contract
    guard saw zero resharding copies."""
    import jax

    if len(jax.devices()) < 8:
        import pytest

        pytest.skip("needs 8 virtual devices")
    from handyrl_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    env, model, svc, client, obs, batch = _real_service(
        mesh=mesh, fsdp=True)
    try:
        _wait_healthy(client, svc)
        served = client.wrap(model, epoch=1)
        local = model.inference_batch(batch, None)

        out1 = served.inference_batch(batch, None)
        out2 = served.inference_batch(batch, None)
        # tp-partitioned contractions drift 3e-6..6e-6 run-to-run on
        # this CPU stack (thread-count dependent): the bound matches
        # the dry-run's TP_ATOL headroom, not the smallest observed
        np.testing.assert_allclose(out1["policy"], local["policy"],
                                   rtol=0, atol=5e-5)
        np.testing.assert_array_equal(out1["policy"], out2["policy"])
        assert client.fallbacks == 0

        stats = svc.stats()
        assert stats["mesh_devices"] == 8
        assert stats["infer_resharding_copies"] == 0
        assert stats["infer_compiles"] >= 1
        # the snapshot rode ONE device_put onto the param shardings
        # (cached on the model object keyed by the sharding set: the
        # routed-LRU contract), and fsdp genuinely distributed at
        # least one leaf
        cached = getattr(model, "_infer_placed", None)
        assert cached is not None and cached[0] is svc._infer_sh
        assert any("dp" in tuple(l.sharding.spec)
                   for l in jax.tree.leaves(cached[1]))
    finally:
        svc.close()
        client.close()


def test_single_device_mesh_dispatch_is_bit_identical():
    """The tentpole's compatibility floor: a 1-device mesh compiles
    the SAME program as the mesh-less dispatch — outputs bit-match
    both the no-mesh service forward and plain local inference."""
    import jax

    from handyrl_tpu.environment import make_env
    from handyrl_tpu.models import TPUModel
    from handyrl_tpu.parallel import MeshSpec, make_mesh
    from handyrl_tpu.pipeline.service import InferenceService

    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(0), seed=0)
    cfg = PipelineConfig.from_config({"mode": "on"})
    batch = jax.tree.map(
        lambda a: np.stack([np.asarray(a)] * 8), env.observation(0))

    one = make_mesh(MeshSpec(dp=1), devices=jax.devices()[:1])
    svc_mesh = InferenceService(model, cfg, epoch=1, mesh=one)
    svc_plain = InferenceService(model, cfg, epoch=1)
    try:
        # no cache scrub needed: _placed_params keys its cache by the
        # service's sharding set, so crossing services re-places
        out_mesh = svc_mesh._forward(model, batch)
        out_plain = svc_plain._forward(model, batch)
        local = model.inference_batch(batch, None)
        for key, ref in local.items():
            if ref is None:
                continue
            np.testing.assert_array_equal(
                np.asarray(out_mesh[key]), np.asarray(ref))
            np.testing.assert_array_equal(
                np.asarray(out_plain[key]), np.asarray(ref))
        assert svc_mesh.shard_guard.copies == 0
    finally:
        svc_mesh.close()
        svc_plain.close()


def test_epoch_pinned_wrapper_skips_a_mismatched_service():
    """A wrapper pinned to another epoch answers locally WITHOUT a
    transport round trip — pinned eval seats and league opponents can
    never act on the newest policy by accident."""
    env, model, svc, client, obs, batch = _real_service()
    try:
        _wait_healthy(client, svc)
        pinned = client.wrap(model, epoch=99)   # service holds epoch 1
        before = svc.stats()["requests"]
        out = pinned.inference_batch(batch, None)
        np.testing.assert_allclose(
            out["policy"], model.inference_batch(batch, None)["policy"],
            rtol=1e-5)
        assert svc.stats()["requests"] == before  # no request shipped
    finally:
        svc.close()
        client.close()


def test_service_death_falls_back_and_respawn_resumes():
    """The supervised-fault contract end to end, in-process: kill the
    service (chaos shape: no parting beat) -> the client detects the
    stale board and answers locally; respawn -> the client returns to
    the served path on its own."""
    import time

    env, model, svc, client, obs, batch = _real_service()
    try:
        _wait_healthy(client, svc)
        served = client.wrap(model, epoch=1)
        local = model.inference_batch(batch, None)

        svc.inject_kill()
        deadline = time.monotonic() + 3.0
        while svc.alive:
            assert time.monotonic() < deadline, "kill never landed"
            time.sleep(0.01)
        time.sleep(0.5)  # past fallback_after: the board is stale now
        assert not client.healthy()
        out = served.inference_batch(batch, None)  # local fallback
        np.testing.assert_allclose(out["policy"], local["policy"],
                                   rtol=1e-5)
        assert client.fallbacks >= 1

        svc.respawn()
        _wait_healthy(client, svc)
        assert svc.board.generation == 1
        before = svc.stats()["rows_served"]
        out = served.inference_batch(batch, None)  # served again
        np.testing.assert_allclose(out["policy"], local["policy"],
                                   rtol=1e-5)
        assert svc.stats()["rows_served"] > before
    finally:
        svc.close()
        client.close()


def test_client_degrades_after_repeated_reply_timeouts():
    """A service that BEATS but never lands replies (reply slot too
    small for the output frame, a mistakenly-reaped client) must cost
    a few timed-out steps, not one full deadline per step forever:
    the client degrades itself, short-circuits further requests, and
    re-probes only on the service's next incarnation."""
    import time as _time

    svc, clock, model = _make_service(window=0.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        desc = svc.attach(spec)
        cfg = PipelineConfig.from_config(
            {"mode": "on", "batch_window": 0.001,
             "fallback_after": 0.05})
        client = PipelineClient(desc, cfg)
        svc.board.beat(epoch=1)  # alive — but nothing serves requests

        def beat_and_wait():
            # keep the board fresh while the client waits out its
            # reply deadline (the service "is up", replies never come)
            svc.board.beat(epoch=1)
            _time.sleep(1e-3)
        client.sleep = lambda dt: beat_and_wait()

        leaves = [np.zeros((1, 2), np.float32)]
        for _ in range(client.DEGRADE_AFTER):
            assert client.request(leaves) is None
        assert client.degraded
        took = []
        for _ in range(3):     # the best of three: five other test
            t0 = _time.monotonic()   # workers share this host's cores
            assert client.request(leaves) is None   # short-circuits now
            took.append(_time.monotonic() - t0)
        assert min(took) < 0.04                 # no deadline burned
        svc.board.bump_generation()             # "respawn"
        assert client.usable()                  # re-probes next time
        assert not client.degraded
        client.close()
    finally:
        svc.close()


def test_idle_clients_are_reaped_and_rings_reclaimed():
    """A client silent on both rings past CLIENT_IDLE_REAP (dead
    worker) leaves the live set immediately and its rings close after
    the graveyard grace — later pushes from a stale mapping are
    refused, never crash."""
    svc, clock, model = _make_service(window=0.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        desc = svc.attach(spec)
        stale = ShmRing.attach(**desc["req"])  # the dead worker's map
        clock.now = svc.CLIENT_IDLE_REAP + 1.0
        assert svc._reap_idle()                # removed from live set
        assert svc.stats()["clients"] == 0
        assert svc.stats()["clients_reaped"] == 1
        clock.now += svc.GRAVE_GRACE + 1.0
        svc._reap_idle()                       # graveyard close
        # the learner-side (owner) ring is closed; the dead worker's
        # own mapping pushes into a torn-down segment harmlessly —
        # owner-side accessors read as empty/refused
        assert stale.push(b"x")  # its own mapping still writes...
        stale.close()
        # ...but a fresh attach by name must now fail: unlinked
        with pytest.raises(FileNotFoundError):
            ShmRing.attach(**desc["req"])
        svc.attach(spec)                       # new clients still fine
        assert svc.stats()["clients"] == 1
    finally:
        svc.close()


def test_trajectory_ring_feeds_intake_and_spills_when_full():
    env, model, svc, client, obs, batch = _real_service(
        traj_slots=2, traj_slot_mb=1)
    try:
        ep = {"steps": 5, "moment": [b"\x80blob"], "outcome": {0: 1.0}}
        assert client.push_episode(ep)
        assert client.push_episode(ep)
        assert not client.push_episode(ep)   # ring full: spill signal
        assert client.episodes_spilled == 1
        drained = svc.drain_trajectories()
        assert len(drained) == 2 and drained[0]["steps"] == 5
        assert svc.ring_full_count() >= 1    # worker-side count, shm-read
        assert client.push_episode(ep)       # flows again after drain
    finally:
        svc.close()
        client.close()


# ---------------------------------------------------------------------
# shm chaos layer: ChaosRing / ChaosBoard fault injection
# ---------------------------------------------------------------------

def test_chaos_config_validates_shm_keys():
    from handyrl_tpu.resilience import ChaosConfig

    cfg = ChaosConfig.from_config({"shm_tear_prob": 0.5,
                                   "shm_stall_prob": 1.0})
    assert cfg.shm_faults_enabled
    assert not ChaosConfig.from_config({}).shm_faults_enabled
    assert ChaosConfig.from_config(
        {"shm_beat_drop_prob": 0.1}).shm_beat_faults_enabled
    with pytest.raises(ValueError, match="shm_tear_prob"):
        ChaosConfig.from_config({"shm_tear_prob": 1.5})
    with pytest.raises(ValueError, match="shm_beat_delay"):
        ChaosConfig.from_config({"shm_beat_delay": -1.0})
    with pytest.raises(ValueError, match="shm push"):
        ChaosConfig.from_config({"shm_tear_prob": 0.6,
                                 "shm_truncate_prob": 0.6})
    with pytest.raises(ValueError, match="shm beat"):
        ChaosConfig.from_config({"shm_beat_drop_prob": 0.7,
                                 "shm_beat_delay_prob": 0.7})


def test_chaos_ring_tear_injection_leaves_a_real_torn_slot():
    """An injected tear is indistinguishable from a producer SIGKILLed
    mid-RESERVE-THEN-FILL: reservation published (odd stamp, head
    past it), payload absent — and the standard reclaim applies."""
    from handyrl_tpu.resilience import ChaosConfig, ChaosRing

    ring = ShmRing.create(slots=4, slot_bytes=64)
    chaos = ChaosRing(ring, ChaosConfig.from_config(
        {"shm_tear_prob": 1.0, "seed": 1}))
    try:
        assert chaos.push(b"doomed")       # the "producer" died
        assert chaos.torn_injected == 1
        assert ring.pending() and not ring.readable()
        assert ring.pop() is None          # never consumed as data
        assert ring.skip_torn()            # reclaim
        assert ring.torn_count == 1
    finally:
        ring.close()


def test_chaos_ring_full_injection_counts_in_the_header():
    """Forced backpressure looks exactly like a full ring: refused AND
    counted where the consumer side reads it (shm header)."""
    from handyrl_tpu.resilience import ChaosConfig, ChaosRing

    ring = ShmRing.create(slots=4, slot_bytes=64)
    chaos = ChaosRing(ring, ChaosConfig.from_config(
        {"shm_full_prob": 1.0, "seed": 1}))
    try:
        assert not chaos.push(b"refused")
        assert chaos.full_injected == 1
        assert ring.full_count == 1        # consumer-visible
        assert len(ring) == 0              # nothing landed
    finally:
        ring.close()


def test_chaos_ring_truncated_payload_is_skipped_not_crashed():
    """Payload truncation under a complete-looking stamp: the consumer
    decode fails, the slot is skipped (counted torn) and the ring
    flows — at the ring level and through the service's drain."""
    from handyrl_tpu.resilience import ChaosConfig, ChaosRing

    ring = ShmRing.create(slots=4, slot_bytes=1024)
    chaos = ChaosRing(ring, ChaosConfig.from_config(
        {"shm_truncate_prob": 1.0, "seed": 1}))
    try:
        blob = shm_mod.dumps({"payload": list(range(64))})
        assert chaos.push(blob)
        assert chaos.truncated_injected == 1
        assert ring.readable()             # looks complete...
        with pytest.raises(Exception):
            ring.pop(loads=shm_mod.loads_view)  # ...but will not decode
        assert ring.skip_one()             # the consumer's escape
        assert ring.torn_count == 1
        assert ring.push(blob)             # clean producer resumes
        assert ring.pop(loads=shm_mod.loads_view)["payload"][3] == 3
    finally:
        ring.close()

    # RAW request frames detect truncation too: the short view makes
    # np.frombuffer raise (schema demands more bytes than the slot
    # holds) — truncation can never decode silently into garbage obs
    reqring = ShmRing.create(slots=2, slot_bytes=1024)
    try:
        chaos2 = ChaosRing(reqring, ChaosConfig.from_config(
            {"shm_truncate_prob": 1.0, "seed": 1}))
        assert chaos2.push(shm_mod.pack_request(
            1, 2, [np.zeros((2, 4), np.float32)]))
        with pytest.raises(Exception):
            reqring.pop(loads=lambda v: shm_mod.unpack_request(
                v, [((4,), "float32")]))
        assert reqring.skip_one()
        assert reqring.torn_count == 1
    finally:
        reqring.close()


def test_service_drain_skips_corrupt_trajectory_slots():
    """The learner-side degradation ladder for a poisoned slot: the
    drain counts + skips it and later episodes still arrive — one bad
    frame never takes the server loop down."""
    from handyrl_tpu.resilience import ChaosConfig, ChaosRing

    svc, clock, model = _make_service(window=0.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        desc = svc.attach(spec)
        traj = ShmRing.attach(**desc["traj"])
        poison = ChaosRing(traj, ChaosConfig.from_config(
            {"shm_truncate_prob": 1.0, "seed": 1}))
        assert poison.push(shm_mod.dumps({"steps": 1}))   # corrupt
        assert traj.push(shm_mod.dumps({"steps": 2}))     # clean
        drained = svc.drain_trajectories()
        assert [ep["steps"] for ep in drained] == [2]
        assert svc.corrupt == 1
        assert svc.stats()["corrupt_slots"] == 1
        assert svc.epoch_stats()["shm_torn_slots"] == 1
        traj.close()
    finally:
        svc.close()


def test_chaos_ring_stalled_consumer_backs_the_ring_up():
    from handyrl_tpu.resilience import ChaosConfig, ChaosRing

    ring = ShmRing.create(slots=4, slot_bytes=64)
    chaos = ChaosRing(ring, ChaosConfig.from_config(
        {"shm_stall_prob": 1.0, "seed": 1}))
    try:
        assert ring.push(b"waiting")
        assert ring.readable()
        assert chaos.pop() is None         # stalled: item stays queued
        assert chaos.stalls_injected == 1
        assert len(ring) == 1              # nothing consumed
        assert ring.pop() == b"waiting"    # a healthy consumer drains
    finally:
        ring.close()


def test_chaos_board_withholds_and_backdates_beats():
    from handyrl_tpu.resilience import ChaosBoard, ChaosConfig

    board = ShmBoard.create()
    try:
        drop = ChaosBoard(board, ChaosConfig.from_config(
            {"shm_beat_drop_prob": 1.0, "seed": 1}))
        drop.beat(epoch=3, now=100.0)
        assert drop.beats_dropped == 1
        assert board.age(now=100.0) == float("inf")  # never landed

        delay = ChaosBoard(board, ChaosConfig.from_config(
            {"shm_beat_delay_prob": 1.0, "shm_beat_delay": 0.5,
             "seed": 1}))
        delay.beat(epoch=3, now=100.0)
        assert delay.beats_delayed == 1
        assert board.age(now=100.0) == pytest.approx(0.5)  # backdated
        assert delay.epoch == 3            # reads delegate untouched
    finally:
        board.close()


# ---------------------------------------------------------------------
# surge brownout: the worker-side hold / paced drain / spill ladder
# ---------------------------------------------------------------------

def test_client_surge_hold_stages_paced_drain_and_overflow_spill():
    """The shm half of `surge_hold_uploads`: during the hold episodes
    stage in the bounded backlog (overflow spills, stamped + counted);
    after the hold the drain is paced FIFO (stale first, a small
    block per shipped episode); the exit flush ships everything —
    and every episode is accounted for (zero loss)."""
    from handyrl_tpu.pipeline.config import PipelineConfig
    from handyrl_tpu.resilience import ChaosConfig

    svc, svc_clock, model = _make_service(window=0.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        desc = svc.attach(spec)
        cfg = PipelineConfig.from_config(
            {"mode": "on", "traj_slots": 4, "traj_slot_mb": 1})
        chaos = ChaosConfig.from_config(
            {"surge_epoch": 2, "surge_hold_uploads": 30.0})
        clock = _FakeClock()
        client = PipelineClient(desc, cfg, clock=clock,
                                sleep=clock.sleep, chaos=chaos)
        try:
            # pre-surge jobs do not trigger (opponent seats are -1)
            client.note_jobs([{"model_id": {0: 1, 1: -1}}, None])
            assert not client.holding()
            client.note_jobs([{"model_id": {0: 2, 1: 2}}])
            assert client.holding()

            # 7 episodes during the hold: backlog caps at traj_slots
            # (4); the 3 oldest spill — stamped, counted, never lost
            spills = []
            for i in range(7):
                spills += client.ship_episode({"i": i})
            assert [e["i"] for e in spills] == [0, 1, 2]
            assert all(e["shm_spilled"] for e in spills)
            assert client.episodes_spilled == 3
            assert client.episodes_held == 7
            assert svc.drain_trajectories() == []   # nothing shipped

            # hold passes: the drain is paced FIFO — current episode
            # joins the tail, a small block ships from the head
            clock.now = 31.0
            assert client.ship_episode({"i": 7}) == []
            drained = svc.drain_trajectories()
            assert [e["i"] for e in drained] == [3, 4, 5]
            # shipped-while-backlogged episodes carry the live depth
            assert drained[0]["upload_backlog"] == 4

            # exit flush: remaining backlog ships over the ring where
            # it fits, spills the rest — zero loss either way
            spills2 = client.flush_backlog()
            drained2 = svc.drain_trajectories()
            shipped = {e["i"] for e in drained + drained2}
            spilled = {e["i"] for e in spills + spills2}
            assert shipped | spilled == set(range(8))
            assert not shipped & spilled
            assert (client.episodes_shipped + client.episodes_spilled
                    == 8)
        finally:
            client.close()
    finally:
        svc.close()


def test_spill_path_under_sustained_full_ring_pressure():
    """Satellite: the trajectory ring pinned full for a whole epoch —
    every episode arrives via the control-plane spill with ZERO loss
    (counts reconcile exactly), `shm_ring_full_count` and
    `episodes_spilled` both advance, and the drain restores ring
    shipping."""
    from handyrl_tpu.resilience import ChaosConfig, ChaosRing

    env, model, svc, client, obs, batch = _real_service()
    try:
        # pin the ring "full" for the epoch: every push refused and
        # counted, exactly what a consumer that never drains causes
        real_traj = client.traj
        client.traj = ChaosRing(real_traj, ChaosConfig.from_config(
            {"shm_full_prob": 1.0, "seed": 3}))
        spilled = []
        for i in range(20):
            spilled += client.ship_episode({"i": i})
        assert [e["i"] for e in spilled] == list(range(20))
        assert all(e["shm_spilled"] for e in spilled)
        assert client.episodes_spilled == 20
        assert svc.ring_full_count() >= 20       # backpressure, visible
        assert svc.drain_trajectories() == []    # nothing rode shm

        # the pressure lifts: ring shipping resumes on its own
        client.traj = real_traj
        for i in range(20, 30):
            assert client.ship_episode({"i": i}) == []
        drained = svc.drain_trajectories()
        assert [e["i"] for e in drained] == list(range(20, 30))
        # zero loss: every episode took exactly one of the two paths
        assert client.episodes_shipped + client.episodes_spilled == 30
    finally:
        svc.close()
        client.close()


def test_status_snapshot_exposes_shm_counters():
    """The status endpoint's pipeline section carries the brownout /
    degradation counters (torn slots, corrupt slots, shm-vs-spill
    episode split, hold backlog) next to the serving stats."""
    from types import SimpleNamespace

    from handyrl_tpu.learner import Learner

    svc, clock, model = _make_service(window=0.0)
    try:
        learner = Learner.__new__(Learner)
        learner.model_epoch = 3
        learner.episodes_received = 10
        learner.worker = SimpleNamespace(connection_count=lambda: 0)
        learner._run_t0 = 0.0
        learner.fleet = SimpleNamespace(snapshot=lambda: {})
        learner._last_record = None
        learner.infer_service = svc
        learner.episodes_shm = 7
        learner.episodes_spilled = 3
        snap = learner._status_snapshot()
        pipe = snap["pipeline"]
        assert pipe["episodes_shm"] == 7
        assert pipe["episodes_spilled"] == 3
        assert pipe["upload_backlog_peak"] == 0
        assert pipe["shm_torn_slots"] == 0
        assert pipe["corrupt_slots"] == 0
        assert "torn_reclaimed" in pipe and "clients_reaped" in pipe
    finally:
        svc.close()


# ---------------------------------------------------------------------
# real-kill torn-slot regression: SIGKILL a producer mid-slot-write
# ---------------------------------------------------------------------

class _StallingParts:
    """A parts sequence for ShmRing.push whose SECOND iteration (the
    write loop — the first computes the length) writes one chunk,
    signals the parent, then blocks: push is left mid-RESERVE-THEN-
    FILL (odd stamp down, head bumped, payload half-written) at the
    exact moment the parent's SIGKILL lands.  No crafted headers: the
    REAL producer code path dies a REAL death mid-slot-write."""

    def __init__(self, ready):
        self.ready = ready
        self.chunks = [b"A" * 8, b"B" * 8]
        self.iterations = 0

    def __iter__(self):
        self.iterations += 1
        if self.iterations == 1:
            return iter(self.chunks)       # push's length pass
        return self._write_pass()

    def _write_pass(self):
        import time

        yield self.chunks[0]               # half the payload lands
        self.ready.set()                   # mid-slot-write: kill me
        time.sleep(600)                    # SIGKILL lands here
        yield self.chunks[1]               # pragma: no cover


def _doomed_producer(desc, ready):
    """Child process: one complete episode, then a push that stalls
    mid-slot-write forever (until the parent SIGKILLs it)."""
    from handyrl_tpu.pipeline import ShmRing
    from handyrl_tpu.pipeline import shm as child_shm

    ring = ShmRing.attach(**desc)
    ring.push(child_shm.dumps({"steps": 5}))
    ring.push(_StallingParts(ready))       # never returns


def test_real_producer_sigkill_mid_slot_write_is_reclaimed():
    """The PR 9 seqlock claim proven against a REAL death: an actual
    producer process is SIGKILLed mid-slot-write (not a crafted
    header), and the consumer detects the odd stamp, skips the slot
    after the grace, counts it, and keeps serving later traffic."""
    import multiprocessing
    import os
    import signal

    ctx = multiprocessing.get_context("spawn")
    svc, clock, model = _make_service(window=0.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        desc = svc.attach(spec)
        ready = ctx.Event()
        proc = ctx.Process(target=_doomed_producer,
                           args=(desc["traj"], ready))
        proc.start()
        try:
            assert ready.wait(60), "producer never reached mid-write"
            os.kill(proc.pid, signal.SIGKILL)   # a real death
        finally:
            proc.join(30)
        assert proc.exitcode == -signal.SIGKILL

        # the complete episode drains; the torn slot stalls the ring
        drained = svc.drain_trajectories()
        assert [ep["steps"] for ep in drained] == [5]
        traj = ShmRing.attach(**desc["traj"])
        assert traj.pending() and not traj.readable()  # odd stamp

        # within the grace the slot is left alone (a live writer may
        # still be mid-frame); past it, the reclaim fires and counts
        assert svc.drain_trajectories() == []
        assert svc.reclaimed == 0
        clock.now = svc.TORN_GRACE + 1.0
        svc.drain_trajectories()
        assert svc.reclaimed == 1
        assert traj.torn_count == 1
        assert svc.epoch_stats()["shm_torn_slots"] == 1

        # training continues: a successor producer ships through the
        # reclaimed ring and the episode arrives intact
        assert traj.push(shm_mod.dumps({"steps": 9}))
        assert [ep["steps"]
                for ep in svc.drain_trajectories()] == [9]
        traj.close()
    finally:
        svc.close()


# ---------------------------------------------------------------------
# tier-1 e2e: chaos-kill the inference server mid-train
# ---------------------------------------------------------------------

def test_pipelined_training_survives_inference_server_kill(
        tmp_path, monkeypatch):
    """DELIBERATELY IN TIER-1 (deterministic, ~2 min): a full local
    training run with the pipeline ON whose inference service is
    chaos-killed at epoch 1 (``chaos.infer_kill_epoch``).  Training
    must complete every epoch anyway — workers bridge the gap on
    local CPU fallback, the learner respawns the service behind its
    backoff, and workers return to the served path (proven by served
    batches AFTER the respawn epoch)."""
    monkeypatch.chdir(tmp_path)

    args = {
        "env_args": {"env": "TicTacToe"},
        "train_args": {
            "turn_based_training": True, "observation": False,
            "gamma": 0.8, "forward_steps": 4, "burn_in_steps": 0,
            "compress_steps": 4, "entropy_regularization": 0.1,
            "entropy_regularization_decay": 0.1,
            "update_episodes": 15, "batch_size": 4,
            "minimum_episodes": 10, "maximum_episodes": 200,
            "epochs": 3, "num_batchers": 1, "eval_rate": 0.1,
            "worker": {"num_parallel": 2}, "lambda": 0.7,
            "policy_target": "VTRACE", "value_target": "VTRACE",
            "seed": 1, "max_update_compiles": 1,
            "metrics_path": "metrics.jsonl",
            # the subsystem under test: pipelined inference + shm
            # trajectories (mode deliberately OMITTED — the repo-wide
            # default is `on`, and this e2e proves the default, not a
            # per-test opt-in), with the service killed at epoch 1 and
            # a fast fallback so the gap is actually exercised
            "pipeline": {"fallback_after": 0.3},
            "chaos": {"infer_kill_epoch": 1},
            "respawn_backoff": 0.5,
        },
        "worker_args": {"num_parallel": 2, "server_address": ""},
    }

    from handyrl_tpu.learner import Learner

    learner = Learner(args)
    learner.run()

    assert learner.model_epoch == 3
    assert learner.trainer.failure is None
    assert learner._infer_killed           # the chaos actually fired
    assert learner._infer_respawns >= 1    # and the respawn recovered it

    with open("metrics.jsonl") as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert len(records) == 3
    for record in records:
        # the pipeline metric contract (docs/observability.md): every
        # epoch reports, even the served-nothing warmup epoch
        assert "infer_batches" in record
        assert "infer_requests" in record
        assert "shm_ring_full_count" in record
        assert "infer_respawns" in record
        assert record["stall_events"] == 0
        assert record["unknown_verbs"] == 0
    # served inference resumed after the kill: the respawn epoch (or a
    # later one) dispatched real batches with their size/wait stats
    post = [r for r in records if r["infer_respawns"] >= 1]
    assert post and sum(r["infer_batches"] for r in post) > 0
    served = [r for r in records if r["infer_batches"] > 0]
    assert served
    for r in served:
        assert r["infer_batch_size_mean"] >= 1
        assert r["infer_batch_size_p95"] >= 1
        assert r["infer_queue_wait_sec"] >= 0
