"""The network serving tier (handyrl_tpu.serving, docs/serving.md):
config validation, the two-planes-one-window batching contract,
multi-model routing, SLO admission control, frontend kill/respawn, and
the tier-1 e2e (a pinned league-seat request served over TCP
bit-matches local inference; an SLO breach sheds instead of
collapsing latency, counted in metrics.jsonl + the status endpoint).
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from handyrl_tpu.pipeline.config import PipelineConfig
from handyrl_tpu.serving import ServingConfig
from handyrl_tpu.serving.client import ServeClient, ServeError, ShedError
from handyrl_tpu.serving.frontend import ServingFrontend, _NetSeat


# ---------------------------------------------------------------------
# config
# ---------------------------------------------------------------------

def test_serving_config_defaults_off_and_validates():
    cfg = ServingConfig.from_config(None)
    assert cfg.mode == "off" and not cfg.enabled
    cfg = ServingConfig.from_config({"mode": "on", "port": 0})
    assert cfg.enabled and cfg.port == 0
    with pytest.raises(ValueError):
        ServingConfig.from_config({"mode": "sideways"})
    with pytest.raises(ValueError):
        ServingConfig.from_config({"bogus_key": 1})
    with pytest.raises(ValueError):
        ServingConfig.from_config({"slo_window": 2})
    with pytest.raises(ValueError):
        ServingConfig.from_config({"breach_admit_every": 1})
    with pytest.raises(ValueError):
        ServingConfig.from_config({"reply_timeout": 0})


def test_train_config_requires_pipeline_for_serving():
    """serving feeds the pipeline batching window: serving on with the
    pipeline explicitly off is a config error, not a silent no-op."""
    from handyrl_tpu.config import Config

    raw = {"env_args": {"env": "TicTacToe"},
           "train_args": {"serving": {"mode": "on", "port": 0},
                          "pipeline": {"mode": "off"}}}
    with pytest.raises(ValueError, match="serving.mode"):
        Config.from_dict(raw)
    # with the pipeline at its default (on) the same section validates
    raw["train_args"].pop("pipeline")
    cfg = Config.from_dict(raw)
    assert cfg.train_args["serving"]["mode"] == "on"


# ---------------------------------------------------------------------
# service: two planes, one window + multi-model routing
# ---------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.now = 0.0
        self.on_advance = None

    def __call__(self):
        return self.now

    def sleep(self, dt):
        self.now += dt
        if self.on_advance is not None:
            self.on_advance(self.now)


class _StubModel:
    """Counts forwards; policy = row index + a model tag so replies
    prove WHICH snapshot answered."""

    module = "stub"

    def __init__(self, tag=0.0):
        self.tag = float(tag)
        self.calls = []

    def inference_batch(self, obs, hidden=None):
        rows = obs.shape[0]
        self.calls.append(rows)
        return {"policy": self.tag + np.tile(
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


def test_network_and_shm_planes_share_one_dispatch():
    """The tentpole contract: a network-plane submit arriving inside
    the batching window joins the SAME bucket-padded jitted forward as
    the shm workers' rows — one dispatch covers both planes."""
    from handyrl_tpu.pipeline import shm as shm_mod
    from handyrl_tpu.pipeline.shm import ShmRing

    svc, clock, model = _make_service(window=1.0)
    try:
        spec = {"leaves": [((2,), "float32")],
                "example": np.zeros(2, np.float32), "rows_max": 4}
        desc = svc.attach(spec)
        req = ShmRing.attach(**desc["req"])
        leaves = [np.full((2, 2), 1.0, np.float32)]
        assert req.push(shm_mod.pack_request(1, 2, leaves))
        req.close()

        seat = _NetSeat("net-0", np.zeros(2, np.float32))
        seq, slot = seat.register()

        def arrive(now):
            if now >= 0.4 and not arrive.done:
                arrive.done = True
                assert svc.submit(
                    seat, seq, 3, [np.zeros((3, 2), np.float32)])
        arrive.done = False
        clock.on_advance = arrive

        assert svc.step()
        assert model.calls == [8]  # 2 shm + 3 net rows, padded to 8
        # shm reply landed on the ring...
        rsp = ShmRing.attach(**desc["rsp"])
        shm_reply = rsp.pop(loads=shm_mod.loads_view)
        rsp.close()
        assert shm_reply[0] == 1 and shm_reply[1] == 1
        np.testing.assert_array_equal(
            shm_reply[2]["policy"][:, 0], [0, 1])
        # ...and the net seat's waiter woke with ITS rows
        assert slot[0].is_set()
        assert slot[1] == 1
        np.testing.assert_array_equal(slot[2]["policy"][:, 0],
                                      [2, 3, 4])
        assert svc.stats()["net_requests"] == 1
    finally:
        svc.close()


def test_epoch_pinned_submit_routes_through_the_resolver():
    """Multi-model routing: a pinned submit dispatches with the
    resolved snapshot's params (its own group), the unpinned one with
    the live model, and an unroutable pin answers typed-unavailable
    (outputs None) instead of timing out."""
    svc, clock, model = _make_service(window=0.0)
    try:
        routed = _StubModel(tag=100.0)
        svc.model_resolver = lambda epoch: (routed if epoch == 7
                                            else None)
        example = np.zeros(2, np.float32)
        live_seat = _NetSeat("net-live", example)
        pin_seat = _NetSeat("net-pin", example)
        lost_seat = _NetSeat("net-lost", example)
        sq1, live_slot = live_seat.register()
        sq2, pin_slot = pin_seat.register()
        sq3, lost_slot = lost_seat.register()
        ones = [np.zeros((1, 2), np.float32)]
        assert svc.submit(live_seat, sq1, 1, ones)
        assert svc.submit(pin_seat, sq2, 1, ones, epoch=7)
        assert svc.submit(lost_seat, sq3, 1, ones, epoch=99)
        assert svc.step()
        assert live_slot[0].is_set() and live_slot[1] == 1
        assert live_slot[2]["policy"][0, 0] == 0.0    # live model
        assert pin_slot[0].is_set() and pin_slot[1] == 7
        assert pin_slot[2]["policy"][0, 0] == 100.0   # routed snapshot
        assert lost_slot[0].is_set()
        assert lost_slot[2] is None                   # typed unavailable
        assert model.calls and routed.calls           # two dispatches
    finally:
        svc.close()


def test_live_epoch_pin_normalizes_into_the_unpinned_group():
    """A pin naming the LIVE snapshot joins the unpinned group's
    forward — identical-params traffic must not split into two
    dispatches and re-pay the overhead the shared window amortizes."""
    svc, clock, model = _make_service(window=0.0)
    try:
        example = np.zeros(2, np.float32)
        a, b = _NetSeat("net-a", example), _NetSeat("net-b", example)
        sq_a, slot_a = a.register()
        sq_b, slot_b = b.register()
        ones = [np.zeros((1, 2), np.float32)]
        assert svc.submit(a, sq_a, 1, ones)           # unpinned
        assert svc.submit(b, sq_b, 1, ones, epoch=1)  # pinned to live
        assert svc.step()
        assert model.calls == [8]  # ONE bucket-padded forward
        assert slot_a[0].is_set() and slot_a[1] == 1
        assert slot_b[0].is_set() and slot_b[1] == 1
    finally:
        svc.close()


# ---------------------------------------------------------------------
# frontend admission / SLO (no sockets: the logic on a stub service)
# ---------------------------------------------------------------------

class _StubEnv:
    def players(self):
        return [0]

    def reset(self):
        pass

    def observation(self, player):
        return np.zeros(2, np.float32)


class _StubService:
    def __init__(self):
        self.alive = True
        self.cfg = PipelineConfig.from_config({"max_batch": 64})

    def submit(self, *a, **k):
        return True


def _frontend(**over):
    cfg = ServingConfig.from_config({
        "mode": "on", "port": 0, "slo_ms": 10.0, "slo_window": 8,
        "max_inflight": 4, "breach_admit_every": 4, **over})
    return ServingFrontend(_StubService(), _StubEnv(), cfg)


def test_admission_sheds_on_breach_with_a_trickle():
    fe = _frontend()
    # window below the SLO: full admission
    for _ in range(8):
        fe._observe(1.0)
    assert fe._admit() is None and not fe._breached
    # window p99 over the SLO: breached, shed all but every 4th
    for _ in range(8):
        fe._observe(50.0)
    assert fe._breached
    outcomes = [fe._admit() for _ in range(8)]
    assert outcomes.count("slo") == 6      # 2 of 8 trickle through
    assert outcomes.count(None) == 2
    # recovery: fast requests pull the window p99 back under
    for _ in range(8):
        fe._observe(1.0)
    assert not fe._breached
    assert fe._admit() is None


def test_admission_sheds_on_inflight_cap_and_dead_service():
    fe = _frontend()
    fe.inflight = fe.cfg.max_inflight
    assert fe._admit() == "overload"
    fe.inflight = 0
    fe.service.alive = False
    assert fe._admit() == "service_down"


def test_admit_reserves_the_inflight_slot_atomically():
    """Admission RESERVES the inflight slot inside the cap check's
    lock section, so N concurrent handlers cannot all pass the check
    before any of them counts — exactly max_inflight admissions fit,
    and _release reopens the gate."""
    fe = _frontend()
    for _ in range(fe.cfg.max_inflight):
        assert fe._admit() is None
    assert fe.inflight == fe.cfg.max_inflight
    assert fe._admit() == "overload"
    fe._release()
    assert fe._admit() is None
    assert fe.inflight == fe.cfg.max_inflight


def test_epoch_stats_reduce_and_reset():
    fe = _frontend()
    fe._count("ok")
    fe._count("shed", "slo")
    fe._count("error")
    with fe._lock:
        fe._epoch_counts["submitted"] = 3
    fe._observe(2.0)
    out = fe.epoch_stats()
    assert out["serve_requests"] == 3
    assert out["serve_ok"] == 1 and out["serve_shed"] == 1 \
        and out["serve_errors"] == 1
    assert out["serve_p50_ms"] > 0
    # reset: the next epoch starts from zero, cumulative stats persist
    again = fe.epoch_stats()
    assert again["serve_requests"] == 0
    assert "serve_p50_ms" not in again
    stats = fe.stats()
    assert stats["submitted"] == 0  # _count alone doesn't submit
    assert stats["ok"] == 1 and stats["shed_by"] == {"slo": 1}


# ---------------------------------------------------------------------
# frontend end to end over real TCP (stub model, real service thread)
# ---------------------------------------------------------------------

def _wait(cond, msg, deadline=10.0):
    end = time.monotonic() + deadline
    while not cond():
        assert time.monotonic() < end, msg
        time.sleep(0.01)


def _real_stack(model=None, **serving_over):
    from handyrl_tpu.pipeline.service import InferenceService

    env = _StubEnv()
    model = model or _StubModel()
    pcfg = PipelineConfig.from_config({
        "mode": "on", "batch_window": 0.001, "max_batch": 16})
    svc = InferenceService(model, pcfg, epoch=1)
    svc.start()
    scfg = ServingConfig.from_config({
        "mode": "on", "port": 0, "slo_ms": 0.0, "reply_timeout": 3.0,
        **serving_over})
    fe = ServingFrontend(svc, env, scfg)
    fe.start()
    return env, model, svc, fe


def test_served_requests_over_tcp_and_typed_failures():
    env, model, svc, fe = _real_stack()
    client = None
    try:
        client = ServeClient("127.0.0.1", fe.port, timeout=5.0)
        # single-obs round trip (row dim added/stripped by the client)
        reply = client.infer(np.zeros(2, np.float32))
        assert reply["epoch"] == 1
        assert reply["outputs"]["policy"].shape == (3,)
        # row-batched round trip
        batch = np.zeros((4, 2), np.float32)
        reply = client.infer_batch(batch)
        assert reply["outputs"]["policy"].shape == (4, 3)
        # stats verb answers the reconciliation counters
        stats = client.stats()
        assert stats["submitted"] >= 2
        assert stats["submitted"] == (stats["ok"] + stats["shed"]
                                      + stats["errors"])
        # malformed schema: typed error, connection survives
        with pytest.raises(ServeError, match="bad request"):
            client.infer_batch(np.zeros((2, 9), np.float32))
        # unroutable pin: typed error (no resolver installed)
        with pytest.raises(ServeError, match="unavailable"):
            client.infer_batch(batch, epoch=42)
        # the connection still serves after both failures
        assert client.infer_batch(batch)["epoch"] == 1
    finally:
        if client is not None:
            client.close()
        fe.close()
        svc.close()


def test_service_kill_sheds_typed_then_respawn_resumes():
    """The chaos ladder, serving-tier view: a killed inference service
    turns arrivals into typed service_down sheds (counted, never
    silent); after respawn the same connection serves again."""
    env, model, svc, fe = _real_stack()
    client = None
    try:
        client = ServeClient("127.0.0.1", fe.port, timeout=5.0)
        obs = np.zeros(2, np.float32)
        assert client.infer(obs)["epoch"] == 1
        svc.inject_kill()
        deadline = time.monotonic() + 3.0
        while svc.alive:
            assert time.monotonic() < deadline, "kill never landed"
            time.sleep(0.01)
        with pytest.raises(ShedError) as err:
            client.infer(obs)
        assert err.value.reason == "service_down"
        assert fe.stats()["shed_by"].get("service_down", 0) >= 1
        svc.respawn()
        assert client.infer(obs)["epoch"] == 1   # served again
        stats = fe.stats()
        assert stats["submitted"] == (stats["ok"] + stats["shed"]
                                      + stats["errors"])
    finally:
        if client is not None:
            client.close()
        fe.close()
        svc.close()


def test_service_kill_under_load_answers_every_request_typed():
    """The kill drill with requests in flight: four closed-loop
    clients, the inference service killed under them and respawned.
    No request is lost — each comes back ok, a typed ``service_down``
    shed or a typed error, requests parked at the kill are answered
    by the respawned incarnation — serving resumes on the same
    connections, and the frontend's counters equal the clients'."""
    env, model, svc, fe = _real_stack()
    seen = {"ok": 0, "shed": [], "error": 0, "lost": []}
    lock, stop = threading.Lock(), threading.Event()

    def load():
        client = ServeClient("127.0.0.1", fe.port, timeout=10.0)
        obs = np.zeros(2, np.float32)
        try:
            while not stop.is_set():
                try:
                    client.infer(obs)
                    with lock:
                        seen["ok"] += 1
                except ShedError as err:
                    with lock:
                        seen["shed"].append(err.reason)
                    time.sleep(0.005)   # a shed client backs off
                except ServeError:
                    with lock:
                        seen["error"] += 1
                except Exception as exc:    # transport: a lost request
                    with lock:
                        seen["lost"].append(repr(exc))
                    return
        finally:
            client.close()

    threads = [threading.Thread(target=load, daemon=True)
               for _ in range(4)]
    try:
        for t in threads:
            t.start()
        _wait(lambda: seen["ok"] >= 20, "load never warmed")
        svc.inject_kill()
        _wait(lambda: not svc.alive, "kill never landed")
        _wait(lambda: seen["shed"], "the gap shed nothing")
        ok_at_respawn = seen["ok"]
        svc.respawn()
        _wait(lambda: seen["ok"] >= ok_at_respawn + 20,
              "serving never resumed")
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        stats = fe.stats()
        fe.close()
        svc.close()
    assert not any(t.is_alive() for t in threads)
    assert seen["lost"] == []
    assert set(seen["shed"]) == {"service_down"}
    assert stats["submitted"] == (seen["ok"] + len(seen["shed"])
                                  + seen["error"])
    assert stats["ok"] == seen["ok"]
    assert stats["shed_by"] == {"service_down": len(seen["shed"])}
    assert stats["errors"] == seen["error"]


def test_arrivals_above_capacity_shed_typed_and_reconcile():
    """Overload over real TCP: more concurrent clients than
    ``max_inflight`` against a forward that is held shut.  Exactly
    ``max_inflight`` arrivals are admitted and every other one is
    answered at once with a typed ``overload`` shed — none queues
    behind the held forward, none is dropped; then, with arrivals
    still above the cap, every request of every client comes back as
    exactly one of ok / shed, and the frontend's counters reconcile
    with what the clients saw."""
    cap, n_clients, rounds = 2, 6, 20
    hold, gate = threading.Event(), threading.Event()

    class _HeldModel(_StubModel):
        def inference_batch(self, obs, hidden=None):
            if hold.is_set():
                assert gate.wait(10.0), "the test never opened the gate"
            return super().inference_batch(obs, hidden)

    env, model, svc, fe = _real_stack(
        _HeldModel(), reply_timeout=20.0, max_inflight=cap)
    seen = {"ok": 0, "overload": 0, "other": []}
    lock = threading.Lock()
    first = {}      # what the clients had seen when the held round ended
    first_done = threading.Barrier(
        n_clients + 1, action=lambda: first.update(seen))

    def one(client, obs):
        try:
            client.infer(obs)
            outcome = "ok"
        except ShedError as err:
            outcome = err.reason
        except Exception as exc:     # typed error or a lost request
            outcome = repr(exc)
        with lock:
            if outcome in ("ok", "overload"):
                seen[outcome] += 1
            else:
                seen["other"].append(outcome)

    def load():
        client = ServeClient("127.0.0.1", fe.port, timeout=30.0)
        obs = np.zeros(2, np.float32)
        try:
            one(client, obs)
            first_done.wait(30.0)
            for _ in range(rounds):
                one(client, obs)
        finally:
            client.close()

    threads = [threading.Thread(target=load, daemon=True)
               for _ in range(n_clients)]
    try:
        # past the frontend's own warm request, the forward is held
        _wait(lambda: model.calls, "frontend never warmed")
        hold.set()
        for t in threads:
            t.start()
        # the cap's worth of arrivals sit admitted, the rest must
        # already have their typed answer
        _wait(lambda: fe.stats()["shed"] >= n_clients - cap,
              "the arrivals above the cap were not shed")
        held = fe.stats()
        assert held["submitted"] == n_clients
        assert held["shed_by"] == {"overload": n_clients - cap}
        assert held["ok"] == 0 and held["errors"] == 0
        assert fe.inflight == cap
        gate.set()
        first_done.wait(30.0)
        assert first["ok"] == cap
        assert first["overload"] == n_clients - cap
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        gate.set()
        stats = fe.stats()
        fe.close()
        svc.close()
    # every request of every client was answered, and typed
    total = n_clients * (1 + rounds)
    assert seen["other"] == []
    assert seen["ok"] + seen["overload"] == total
    # and the frontend counted exactly what the clients saw
    assert stats["submitted"] == total
    assert stats["ok"] == seen["ok"] and stats["errors"] == 0
    assert stats["shed"] == seen["overload"]
    assert stats["shed_by"] == {"overload": seen["overload"]}
    assert fe.inflight == 0


def test_connection_cap_refuses_at_accept():
    """Connects past serving.max_connections are closed at accept
    (counted) instead of growing one handler thread each — a
    connection sweep against the public port cannot starve the
    colocated learner; live connections keep serving."""
    env, model, svc, fe = _real_stack(max_connections=2)
    clients = []
    try:
        obs = np.zeros(2, np.float32)
        for _ in range(2):
            c = ServeClient("127.0.0.1", fe.port, timeout=5.0)
            assert c.infer(obs)["epoch"] == 1  # handler live
            clients.append(c)
        refused = ServeClient("127.0.0.1", fe.port, timeout=3.0)
        with pytest.raises(Exception):
            refused.infer(obs)  # closed at accept: the call fails
        refused.close()
        deadline = time.monotonic() + 3.0
        while fe.stats()["connections_refused"] < 1:
            assert time.monotonic() < deadline, "refusal never counted"
            time.sleep(0.01)
        # the admitted connections still serve
        assert clients[0].infer(obs)["epoch"] == 1
    finally:
        for c in clients:
            c.close()
        fe.close()
        svc.close()


def test_frontend_kill_and_respawn_cycle():
    """The frontend's own supervised-fault drill: inject_kill severs
    the acceptor + live connections like a crashed process; respawn
    rebinds and serves fresh connections (incarnation bumped)."""
    env, model, svc, fe = _real_stack()
    client = None
    try:
        client = ServeClient("127.0.0.1", fe.port, timeout=2.0)
        obs = np.zeros(2, np.float32)
        assert client.infer(obs)["epoch"] == 1
        fe.inject_kill()
        deadline = time.monotonic() + 3.0
        while fe.alive:
            assert time.monotonic() < deadline, "kill never landed"
            time.sleep(0.01)
        # the severed connection fails loudly, not silently
        with pytest.raises(Exception):
            client.infer(obs)
        client.close()
        fe.respawn()
        assert fe.alive and fe.generation == 1
        client = ServeClient("127.0.0.1", fe.port, timeout=5.0)
        assert client.infer(obs)["epoch"] == 1
    finally:
        if client is not None:
            client.close()
        fe.close()
        svc.close()


# ---------------------------------------------------------------------
# tier-1 e2e: pinned league seat bit-match + SLO-breach drill
# ---------------------------------------------------------------------

def test_served_league_seat_bitmatches_and_slo_sheds(
        tmp_path, monkeypatch):
    """DELIBERATELY IN TIER-1 (deterministic, ~1-2 min): a full local
    training run with the serving tier armed.

    Two acceptance drills ride one run: (1) a request pinned to epoch
    1 — the league/eval-seat shape — served over the network frontend
    while the live model has moved on BIT-MATCHES local inference on
    the same checkpoint (multi-model routing + one-jit bit
    compatibility); (2) with a deliberately impossible SLO
    (slo_ms ~ 1us) the admission control SHEDS under load — typed
    replies, counted in metrics.jsonl (serve_shed) and on the status
    endpoint — instead of letting latency collapse silently."""
    import urllib.request

    from handyrl_tpu.connection import find_free_port
    from handyrl_tpu.durability import read_verified
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.learner import Learner
    from handyrl_tpu.models import TPUModel

    monkeypatch.chdir(tmp_path)
    status_port = find_free_port()
    args = {
        "env_args": {"env": "TicTacToe"},
        "train_args": {
            "turn_based_training": True, "observation": False,
            "gamma": 0.8, "forward_steps": 4, "burn_in_steps": 0,
            "compress_steps": 4, "entropy_regularization": 0.1,
            "entropy_regularization_decay": 0.1,
            "update_episodes": 20, "batch_size": 4,
            "minimum_episodes": 10, "maximum_episodes": 200,
            "epochs": 4, "num_batchers": 1, "eval_rate": 0.1,
            "worker": {"num_parallel": 2}, "lambda": 0.7,
            "policy_target": "VTRACE", "value_target": "VTRACE",
            "seed": 1, "metrics_path": "metrics.jsonl",
            "status_port": status_port,
            # the subsystem under test: the network frontend on an
            # ephemeral port with an impossible SLO so the breach
            # drill triggers deterministically once the window warms
            "serving": {"mode": "on", "port": 0, "slo_ms": 0.001,
                        "slo_window": 8, "breach_admit_every": 4,
                        "reply_timeout": 5.0},
        },
        "worker_args": {"num_parallel": 2, "server_address": ""},
    }

    learner = Learner(args)
    assert learner.serve_frontend is not None
    port = learner.serve_frontend.port
    runner = threading.Thread(target=learner.run, daemon=True)
    runner.start()
    client = None
    try:
        # wait until epoch 1's checkpoint is committed AND the live
        # model has moved past it, so the pin genuinely routes
        deadline = time.monotonic() + 120
        while not (learner.model_epoch >= 2
                   and os.path.exists("models/1.ckpt")):
            assert time.monotonic() < deadline, "epoch 2 never came"
            assert runner.is_alive(), "learner died early"
            time.sleep(0.2)

        env = make_env({"env": "TicTacToe"})
        env.reset()
        obs = np.asarray(env.observation(env.players()[0]))
        batch = np.stack([obs] * 8)   # 8 rows = the bucket floor:
        #                               served + local shapes identical
        client = ServeClient("127.0.0.1", port, timeout=10.0)

        # -- drill 1: pinned league seat bit-matches local inference --
        local = TPUModel(env.net())
        local.params = read_verified("models/1.ckpt")["params"]
        expect = local.inference_batch(batch, None)
        got = None
        for _ in range(30):   # the first 8+ requests warm the window
            try:
                reply = client.infer_batch(batch, epoch=1)
            except ShedError:
                continue      # breach may already be active
            assert reply["epoch"] == 1
            got = reply["outputs"]
            break
        assert got is not None, "every pinned request was shed"
        if learner.infer_service.stats()["mesh_devices"] == 1:
            # single-device dispatch: the bit-exact contract holds
            # verbatim (production single-chip serving)
            np.testing.assert_array_equal(
                np.asarray(got["policy"]),
                np.asarray(expect["policy"]))
            np.testing.assert_array_equal(
                np.asarray(got["value"]) if "value" in got else 0,
                np.asarray(expect["value"]) if "value" in expect else 0)
        else:
            # GSPMD dispatch (this suite's virtual 8-device mesh
            # auto-engages dp): the row-sharded conv picks different
            # backend kernels than the single-device reference, so
            # cross-PATH comparison is float32-epsilon, not bitwise —
            # measured ~1e-6 on this CPU stack.  The product
            # invariant is unharmed: pinned and live requests ride
            # the SAME compiled program (mutual consistency is
            # exact), and IS corrections use the probabilities the
            # reply actually carried.  test_pipeline's served==local
            # tests keep the bitwise contract on the unsharded path
            np.testing.assert_allclose(
                np.asarray(got["policy"]),
                np.asarray(expect["policy"]), rtol=0, atol=5e-6)
            # or-0 on BOTH sides, like the exact branch: a reply that
            # drops the value head while local inference has one must
            # fail here, not be skipped
            np.testing.assert_allclose(
                np.asarray(got["value"]) if "value" in got else 0,
                np.asarray(expect["value"]) if "value" in expect else 0,
                rtol=0, atol=5e-6)
            # the sharded plane must SAY it is sharded, with the guard
            # contract intact (0 resharding copies at this point)
            stats = learner.infer_service.stats()
            assert stats["mesh_devices"] > 1
            assert stats["infer_resharding_copies"] == 0

        # -- drill 2: the impossible SLO sheds under load --
        sheds = oks = 0
        for _ in range(60):
            try:
                client.infer_batch(batch)
                oks += 1
            except ShedError as exc:
                assert exc.reason == "slo"
                sheds += 1
        assert sheds > 0, "SLO breach never shed"
        assert oks > 0, "the breach trickle admitted nothing"

        # status endpoint counts the sheds (cumulative view) and the
        # /healthz probe answers without the full snapshot
        with urllib.request.urlopen(
                f"http://127.0.0.1:{status_port}/", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["serving"]["shed"] >= sheds
        assert snap["serving"]["shed_by"].get("slo", 0) > 0
        assert snap["serving"]["submitted"] == (
            snap["serving"]["ok"] + snap["serving"]["shed"]
            + snap["serving"]["errors"])
        with urllib.request.urlopen(
                f"http://127.0.0.1:{status_port}/healthz",
                timeout=10) as r:
            assert json.loads(r.read()) == {"ok": True}
    finally:
        if client is not None:
            client.close()
        runner.join(timeout=300)
    assert not runner.is_alive(), "learner never finished"
    assert learner.model_epoch == 4
    assert learner.trainer.failure is None

    with open("metrics.jsonl") as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert len(records) == 4
    for record in records:
        # the serving metric contract (docs/observability.md): every
        # epoch reports, even before the first client connects
        assert "serve_requests" in record
        assert "serve_shed" in record
        assert "serve_qps" in record
        assert "serve_respawns" in record
    assert sum(r["serve_requests"] for r in records) >= 8
    # the breach drill's sheds are COUNTED in the metrics stream
    assert sum(r["serve_shed"] for r in records) > 0
    served = [r for r in records if r.get("serve_ok", 0) > 0]
    assert served
    for r in served:
        assert r["serve_p50_ms"] > 0
        assert r["serve_p99_ms"] >= r["serve_p50_ms"]
