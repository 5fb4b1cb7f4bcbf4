"""The learner's layers measured from inside the program (PR 25):
spans where the work happens (ring ingest, the epoch boundary, the
hand-over, the server's update), their mirror onto the profiler's
clock, the flush policy, the named scopes inside the fused step, and
the reducer for the program's own device traces."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from handyrl_tpu import telemetry
from handyrl_tpu.telemetry import devtrace, spans

SCOPES = ("replay.draw", "replay.gather", "net.forward", "loss.targets",
          "loss.terms", "optimizer")


def _ttt(count=12, seed=3):
    from __graft_entry__ import TTT_CFG, _build_model_and_batch

    model, _, cfg, episodes = _build_model_and_batch(
        batch_size=count, seed=seed, env_name="TicTacToe",
        return_episodes=True)
    return model, dict(cfg), episodes


def _replay(cfg, capacity=64):
    from handyrl_tpu.staging import DeviceReplay

    return DeviceReplay(
        {"turn_based_training": cfg["turn_based_training"],
         "observation": cfg.get("observation", False),
         "forward_steps": cfg["forward_steps"], "burn_in_steps": 0,
         "transfer_dtype": "", "compute_dtype": "float32"},
        capacity, 64 << 20)


def _ring(name=None):
    recs = telemetry.ring_snapshot()
    return [r for r in recs if name is None or r["name"] == name]


def _inside(child, parent):
    return (parent["ts"] - 2e-6 <= child["ts"]
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 2e-6)


# -- ring ingest --------------------------------------------------------

def test_an_empty_ingest_records_no_span():
    _, cfg, _ = _ttt(1)
    telemetry.configure(enabled=True)
    replay = _replay(cfg)
    for _ in range(5):
        replay.ingest(max_episodes=8)
    assert _ring() == []


def test_ingest_records_one_span_with_its_three_children_inside():
    _, cfg, episodes = _ttt(5)
    telemetry.configure(enabled=True)
    replay = _replay(cfg)
    replay.offer(episodes)
    replay.ingest(max_episodes=8)
    (parent,) = _ring("trainer.ingest")
    assert parent["attrs"]["episodes"] == 5
    assert replay.episodes_seen == 5
    children = [r for r in _ring() if r["name"].startswith("ingest.")]
    assert [c["name"] for c in children] == [
        "ingest.decompress", "ingest.pad", "ingest.append"]
    for child in children:
        assert _inside(child, parent)
        assert child["tid"] == parent["tid"]
    # the children follow one another: no overlap on the one thread
    for a, b in zip(children, children[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 2e-6
    replay.ingest(max_episodes=8)       # nothing waits: nothing more
    assert len(_ring("trainer.ingest")) == 1


def test_wait_ms_is_one_per_episode_and_shed_stamps_go_with_them():
    _, cfg, episodes = _ttt(6)
    now = {"t": 100.0}
    telemetry.configure(enabled=True, clock=lambda: now["t"])
    replay = _replay(cfg)
    replay.pending_cap = 4
    replay.offer(episodes[:3])          # stamped 100.0
    now["t"] = 101.0
    replay.offer(episodes[3:])          # stamped 101.0; sheds the oldest 2
    assert replay.dropped == 2
    assert len(replay.pending) == len(replay._offered_at) == 4
    now["t"] = 101.5
    replay.ingest(max_episodes=8)
    (append,) = _ring("ingest.append")
    assert append["attrs"]["wait_ms"] == [1500.0, 500.0, 500.0, 500.0]
    assert not replay.pending and not replay._offered_at


def test_offer_keeps_its_stamps_in_step_when_telemetry_is_off():
    _, cfg, episodes = _ttt(3)
    replay = _replay(cfg)
    replay.offer(episodes + [None])
    assert len(replay.pending) == len(replay._offered_at) == 3
    replay.ingest()
    assert replay.size == 3 and not replay._offered_at
    assert _ring() == []


# -- epoch boundary, hand-over, the server's update ---------------------

def test_one_epoch_leaves_boundary_handoff_and_server_update_spans(
        tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from test_durability import _train_args

    from handyrl_tpu.learner import Learner

    _, _, episodes = _ttt(12)
    args = _train_args(extra_train={"mesh": {"dp": 1},
                                    "device_replay": "on"})
    learner = Learner(args)
    trainer = learner.trainer
    thread = threading.Thread(target=trainer.run, name="trainer")
    server = threading.Thread(target=learner.update, name="server")
    try:
        trainer.device_replay.offer(episodes)
        thread.start()
        deadline = time.time() + 120
        while trainer.steps < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert trainer.steps >= 2, trainer.failure
        server.start()
        server.join(timeout=120)
        assert not server.is_alive()
    finally:
        trainer.request_shutdown()
        thread.join(timeout=60)
        if learner.stall_watchdog is not None:
            learner.stall_watchdog.stop()
        if learner.wal is not None:
            learner.wal.close()
    (boundary,) = _ring("trainer.boundary")
    parts = [r for r in _ring() if r["name"].startswith("boundary.")]
    assert [p["name"] for p in parts] == [
        "boundary.drain", "boundary.snapshot", "boundary.checkpoint"]
    assert all(_inside(p, boundary) and p["tid"] == boundary["tid"]
               for p in parts)
    (handoff,) = _ring("trainer.handoff")
    assert handoff["tid"] == boundary["tid"]
    assert handoff["ts"] >= boundary["ts"] + boundary["dur"] - 2e-6
    (update,) = _ring("learner.update")
    assert update["tid"] != boundary["tid"]       # the server's thread
    # the server was away at least as long as the hand-over took to come
    assert update["ts"] + update["dur"] >= handoff["ts"] + handoff["dur"]
    # profile_*_sec is still fed, from the one clock
    assert trainer.last_metrics["profile_update_sec"] > 0
    assert "profile_ingest_sec" in trainer.last_metrics
    # learner.update flushed at the boundary: the log holds its own span
    from handyrl_tpu.telemetry.export import collect_run

    _roles, logged = collect_run(str(tmp_path))
    names = {r["name"] for r in logged}
    assert {"learner.update", "trainer.boundary", "trainer.handoff",
            "trainer.ingest", "ingest.append"} <= names


# -- the in-flight ledger, end to end -----------------------------------

def test_two_epochs_leave_the_ledgers_account_in_the_log_and_the_metrics(
        tmp_path, monkeypatch):
    """A tiny train on the CPU: ``trainer.update`` carries ``depth`` and
    ``done``, every boundary's drain leaves nothing in flight and starts
    a ``device.starved`` stretch that the next epoch's first launch
    closes, and metrics.jsonl carries the epoch's account."""
    monkeypatch.chdir(tmp_path)
    from test_durability import _train_args

    from handyrl_tpu.learner import Learner

    _, _, episodes = _ttt(12)
    learner = Learner(_train_args(extra_train={
        "mesh": {"dp": 1}, "device_replay": "on",
        "perf": {"peak_tflops": 1.0, "peak_hbm_gbs": 100.0}}))
    trainer = learner.trainer
    assert trainer.device_replay.inflight is trainer.inflight
    left_in_flight = []
    real_drain = trainer._drain

    def drain(metrics):
        sums = real_drain(metrics)
        left_in_flight.append(len(trainer.inflight._tokens))
        return sums

    trainer._drain = drain
    thread = threading.Thread(target=trainer.run, name="trainer")
    try:
        trainer.device_replay.offer(episodes)
        thread.start()
        for epoch in (1, 2):
            deadline = time.time() + 120
            while trainer.steps < 3 * epoch and time.time() < deadline:
                time.sleep(0.01)
            assert trainer.steps >= 3 * epoch, trainer.failure
            learner.update()
        deadline = time.time() + 120
        while trainer.steps < 8 and time.time() < deadline:
            time.sleep(0.01)        # epoch three's first launches
    finally:
        trainer.request_shutdown()
        thread.join(timeout=60)
        if learner.stall_watchdog is not None:
            learner.stall_watchdog.stop()
        if learner.wal is not None:
            learner.wal.close()
    assert trainer.failure is None
    assert left_in_flight == [0, 0]
    telemetry.flush()
    from handyrl_tpu.telemetry.export import collect_run

    _roles, logged = collect_run(str(tmp_path))
    updates = [r for r in logged if r["name"] == "trainer.update"]
    assert len(updates) == trainer.steps
    assert all(set(r["attrs"]) == {"depth", "done"} for r in updates)
    assert updates[0]["attrs"]["depth"] == 0      # nothing launched yet
    appends = [r for r in logged if r["name"] == "ingest.append"]
    assert appends and all(
        {"depth", "done", "wait_ms"} <= set(r["attrs"]) for r in appends)
    starved = [r for r in logged if r["name"] == "device.starved"]
    drains = [r for r in logged if r["name"] == "boundary.drain"]
    assert len(drains) == 2
    for drain_span in drains:
        # one stretch holds each boundary: begun at the drain's own
        # poll or, where the step is so short that the queue was dry
        # before, at the last dispatch's exit ...
        drained = drain_span["ts"] + drain_span["dur"]
        (stretch,) = [r for r in starved
                      if r["ts"] <= drained < r["ts"] + r["dur"]]
        assert stretch["attrs"]["at"] in ("boundary.drain", "update")
        # ... and lasts through snapshot, checkpoint and hand-over, to
        # the return of the next epoch's first dispatch
        after = min(r["ts"] + r["dur"] for r in updates
                    if r["ts"] > drain_span["ts"])
        assert stretch["ts"] + stretch["dur"] == pytest.approx(
            after, abs=5e-3)
    # the run's first stretch begins at the ledger's first poll (the
    # warm-up's ingest) and holds the step's compile
    assert starved[0]["attrs"]["since_ms"] == 0.0
    assert starved[0]["ts"] + starved[0]["dur"] == pytest.approx(
        updates[0]["ts"] + updates[0]["dur"], abs=5e-3)
    assert starved[0]["dur"] >= updates[0]["dur"] - 5e-3
    with open("metrics.jsonl") as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert len(records) == 2
    for record in records:
        assert record["starved_sec"] > 0
        assert isinstance(record["run_ahead_p50"], int)
        # seconds with a step in flight: no more than the epoch's wall,
        # and with the starved ones the ledger's own wall
        assert 0 < record["device_step_sec"] <= record["epoch_wall_sec"] + 0.05
        assert record["profile_update_sec"] > 0      # dispatch, where it was
        assert record["mfu"] > 0 and record["achieved_tflops"] > 0


# -- the mirror onto the profiler's clock -------------------------------

class _Annotation:
    entered = []
    exited = []

    def __init__(self, name, **kwargs):
        self.name = name

    def __enter__(self):
        _Annotation.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        _Annotation.exited.append(self.name)
        return False


@pytest.fixture()
def annotation():
    _Annotation.entered, _Annotation.exited = [], []
    return _Annotation


def test_the_mirror_is_entered_once_per_live_span(annotation):
    from handyrl_tpu.utils.profiling import SectionTimers

    telemetry.configure(enabled=True, annotate=annotation)
    timers = SectionTimers()
    with telemetry.trace_span("boundary.drain"):
        with timers.section("update"):
            pass
    with timers.section("ingest", span=False):   # seconds only
        pass
    assert annotation.entered == ["hrl:boundary.drain",
                                  "hrl:trainer.update"]
    assert annotation.exited == ["hrl:trainer.update",
                                 "hrl:boundary.drain"]
    assert [r["name"] for r in _ring()] == ["trainer.update",
                                            "boundary.drain"]
    assert timers.snapshot()["ingest"]["n"] == 1
    # spans recorded after the fact are not mirrored
    t0 = telemetry.span_begin()
    telemetry.span_end("anakin.rollout", t0)
    telemetry.record_span("late", 1.0, 2.0)
    telemetry.add_event("mark")
    assert len(annotation.entered) == 2 and len(_ring()) == 5


def test_the_mirror_is_never_entered_when_telemetry_is_off(annotation):
    from handyrl_tpu.utils.profiling import SectionTimers

    telemetry.configure(enabled=False, annotate=annotation)
    timers = SectionTimers()
    with telemetry.trace_span("work"):
        with timers.section("update"):
            pass
    assert annotation.entered == [] and _ring() == []
    assert telemetry.mirror("work") is None
    # the section still feeds profile_update_sec
    assert timers.snapshot()["update"]["n"] == 1


def test_children_are_handed_no_annotation_class():
    state = spans.configure_from_args({"telemetry": True}, role="worker-0")
    assert state.annotate is None


def test_spans_and_devtrace_import_no_jax():
    code = ("import sys; import handyrl_tpu.telemetry.spans, "
            "handyrl_tpu.telemetry.devtrace; "
            "print(any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules))")
    out = subprocess.run([sys.executable, "-c", code], text=True,
                         capture_output=True, timeout=120,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.stdout.strip() == "False", out.stdout + out.stderr


# -- the flush policy ----------------------------------------------------

def _log_lines(path):
    files = [f for f in os.listdir(path) if f.startswith("spans-")]
    if not files:
        return []
    (name,) = files
    with open(os.path.join(path, name)) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_nothing_is_written_between_two_flushes_below_the_cap(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path))
    for i in range(200):                # 12 old file writes' worth
        with telemetry.trace_span("trainer.update"):
            pass
    assert _log_lines(tmp_path) == []
    telemetry.flush()
    assert len(_log_lines(tmp_path)) == 1 + 200      # meta + spans
    for i in range(50):
        telemetry.record_span("x", float(i), 0.5)
    assert len(_log_lines(tmp_path)) == 201
    telemetry.flush()
    assert len(_log_lines(tmp_path)) == 251


def test_a_thread_writes_itself_at_the_cap(tmp_path, monkeypatch):
    monkeypatch.setattr(spans, "_SPAN_BUFFER_CAP", 64)
    telemetry.configure(enabled=True, log_dir=str(tmp_path))
    for i in range(63):
        telemetry.record_span("x", float(i), 0.5)
    assert _log_lines(tmp_path) == []
    telemetry.record_span("x", 63.0, 0.5)
    assert len(_log_lines(tmp_path)) == 1 + 64


def test_a_dump_loses_no_span(tmp_path):
    telemetry.configure(enabled=True, log_dir=str(tmp_path), ring=8)
    for i in range(20):
        telemetry.record_span("x", float(i), 0.5)
    path = telemetry.dump("drill")
    with open(path) as f:
        assert len(json.load(f)["spans"]) == 8      # the ring's bound
    assert len(_log_lines(tmp_path)) == 1 + 20      # the log: all of them


def test_an_exit_loses_no_span(tmp_path):
    code = (
        "from handyrl_tpu import telemetry\n"
        f"telemetry.configure(enabled=True, log_dir={str(tmp_path)!r})\n"
        "for i in range(10):\n"
        "    telemetry.record_span('x', float(i), 0.5)\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
    assert len(_log_lines(tmp_path)) == 1 + 10


# -- named scopes inside the step ---------------------------------------

def _lowered_debug_text(kind):
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import (
        DEFAULT_LR, make_optimizer, make_update_step)
    from handyrl_tpu.staging import (
        _decompress_episode, epoch_sums, make_replay_update_step)

    model, cfg, episodes = _ttt(2)
    if kind == "impact":
        cfg.update(update_algorithm="impact", target_update_interval=4)
    loss_cfg = LossConfig.from_config(
        dict(cfg, observation=cfg.get("observation", False),
             burn_in_steps=0))
    optimizer = make_optimizer(DEFAULT_LR * 16)
    params = jax.eval_shape(lambda: model.params)
    opt_state = jax.eval_shape(optimizer.init, params)
    replay = _replay(cfg)
    buffers = replay._plan_buffers(_decompress_episode(episodes[0]))
    if kind == "update_step":
        rows = jax.ShapeDtypeStruct((4,), jnp.int32)
        batch = jax.eval_shape(
            replay._gather_batch, buffers, rows, rows, rows)
        step = make_update_step(model, loss_cfg, optimizer)
        lowered = step.lower(params, opt_state, batch)
    else:
        step = make_replay_update_step(
            replay, model, loss_cfg, optimizer, "float32", batch_size=4)
        args = [params, opt_state, buffers,
                (jax.ShapeDtypeStruct((3,), jnp.int32),
                 epoch_sums(replay))]
        if kind == "impact":
            args.append(params)
        lowered = step.lower(*args)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("kind", ["standard", "impact", "update_step"])
def test_the_lowered_step_names_its_scopes(kind):
    text = _lowered_debug_text(kind)
    # the host-feed step has no draw and no gather: its batch arrives
    want = SCOPES[2:] if kind == "update_step" else SCOPES
    for scope in want:
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    # the backward pass needs no scope of its own
    assert "transpose(jvp(net.forward))" in text


def test_the_cost_model_keeps_the_compiled_text_with_its_scopes():
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.telemetry.costmodel import CostModel

    @jax.jit
    def step(x):
        with jax.named_scope("net.forward"):
            return jnp.tanh(x) * 2.0

    model = CostModel()
    assert model.hlo_text("step") == ""
    model.on_compile("step", step, (jnp.ones((8, 8)),), {})
    text = model.hlo_text("step")
    assert devtrace.module_name(text) == "jit_step"
    assert any(devtrace.phase_of(name) == "forward"
               for name in devtrace.op_names(text).values())
    assert "hlo_text" not in json.dumps(model.stats())   # status stays small


def test_a_cache_entry_keyed_with_its_metadata_never_answers_stale(tmp_path):
    """JAX keys a cache entry without the program's metadata: the same
    operations under a new scope load the OLD build's text.  Compiled
    under ``metadata_in_key`` (as the trainer compiles the fused step)
    the entry is the build's own."""
    code = """
import jax, jax.numpy as jnp
from handyrl_tpu.utils.compile_cache import metadata_in_key

def build(scoped):
    def step(x):
        if scoped:
            with jax.named_scope("net.forward"):
                return jnp.tanh(x) * 2.0
        return jnp.tanh(x) * 2.0
    return jax.jit(step)

def text(scoped):
    return build(scoped).lower(jnp.ones((8, 8))).compile().as_text()

assert "net.forward" not in text(False)       # the parent fills the cache
assert "net.forward" not in text(True)        # stale: the parent's entry
with metadata_in_key():
    assert "net.forward" in text(True)        # its own entry
assert not jax.config.jax_compilation_cache_include_metadata_in_key
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env=env, capture_output=True)


# -- the reducer for the program's own traces ----------------------------

HLO = """HloModule jit_step, entry_computation_layout={()}

%fused_computation (p: f32[8]) -> f32[8] {
  %inner.1 = f32[8] add(%p, %p), metadata={op_name="jit(step)/jvp(net.forward)/add"}
}

ENTRY %main () -> f32[8] {
  %fusion.1 = u8[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(step)/replay.gather/gather" source_file="staging.py"}
  %copy.2 = u8[8]{0} copy(%fusion.1), metadata={op_name="jit(step)/replay.draw/floor"}
  %while.3 = (s32[], f32[8]) while(%t), metadata={op_name="jit(step)/jvp(net.forward)/while"}
  %while.4 = (s32[], f32[8]) while(%t), metadata={op_name="jit(step)/transpose(jvp(net.forward))/while"}
  %fusion.5 = f32[] fusion(%b), metadata={op_name="jit(step)/loss.targets/mul"}
  %fusion.6 = f32[] fusion(%b), metadata={op_name="jit(step)/jvp(loss.terms)/reduce_sum"}
  %fusion.7 = f32[] fusion(%b), metadata={op_name="jit(step)/transpose(jvp(loss.terms))/mul"}
  %fusion.8 = f32[8] fusion(%c), metadata={op_name="jit(step)/optimizer/sub"}
  ROOT %add.9 = s32[3] add(%s, %one), metadata={op_name="jit(step)/add"}
  %copy.10 = f32[8] copy(%w)
  %copy.12 = pred[64,4] copy(%ring), metadata={op_name="buffers[\\'omask\\']"}
}
"""


def _op(name, start, dur):
    # as the TPU plane names an op: by its HLO text
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", start, dur)


def _plain_trace(steps=2):
    """Two steps of 1000 ns on chip 0, 500 ns apart."""
    mods, ops = [], []
    for k in range(steps):
        t = 10_000.0 + 1500.0 * k
        mods.append(("jit_step(123)", t, 1000.0))
        ops += [_op("fusion.1", t, 100.0), _op("copy.2", t + 100, 50.0),
                _op("while.3", t + 150, 300.0),
                _op("inner.1", t + 160, 100.0),     # nested in the while
                _op("inner.1", t + 260, 100.0),
                _op("while.4", t + 450, 250.0),
                _op("fusion.5", t + 700, 40.0), _op("fusion.6", t + 740, 30.0),
                _op("fusion.7", t + 770, 30.0), _op("fusion.8", t + 800, 100.0),
                _op("add.9", t + 900, 10.0), _op("copy.10", t + 910, 20.0),
                _op("mystery.11", t + 930, 20.0),
                _op("copy.12", t + 950, 30.0)]    # the ring, re-laid
    # another program's op between the steps does not count
    mods.append(("jit_append(9)", 11_100.0, 200.0))
    ops.append(_op("fusion.8", 11_100.0, 200.0))
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": [
                (devtrace.instruction(n), s, d) for n, s, d in ops]}]},
        {"name": "/host:CPU", "lines": []}],
        "op_names": devtrace.op_names(HLO)}


def test_step_phases_split_forward_from_its_transpose_and_sum_to_the_step():
    out = devtrace.step_phases(_plain_trace())
    assert out["steps"] == 2 and out["step_ms"] == pytest.approx(1e-3)
    phases = {k: round(v * 1e6) for k, v in out["phases"].items()}  # ns
    assert phases == {
        "gather": 180,      # replay.gather + replay.draw + the ring's copy
        "forward": 300,     # the while, whole; its body is not counted twice
        "targets": 70,      # loss.targets + loss.terms going forward
        "backward": 280,    # both transposes
        "optimizer": 100,
        "unscoped": 70,     # add.9, copy.10, mystery.11 and 20 ns of gaps
    }
    assert sum(out["phases"].values()) == pytest.approx(out["step_ms"])
    assert round(out["unmatched_ms"] * 1e6) == 20       # mystery.11 only
    assert round(out["op_gap_ms"] * 1e6) == 20          # no op ran at all


@pytest.mark.parametrize("op_names", [
    {},                                             # no text was kept
    {"fusion.1": "jit(step)/jit(main)/gather",      # another build's text:
     "while.4": "jit(step)/transpose(jvp(Net))/while"}])   # no scope in it
def test_step_phases_refuse_a_text_that_names_no_scope(op_names):
    trace = dict(_plain_trace(), op_names=op_names)
    with pytest.raises(ValueError, match="names no scope of the step"):
        devtrace.step_phases(trace)
    with pytest.raises(ValueError, match="no jit_update_step event"):
        devtrace.step_phases(trace, module="jit_update_step")


def test_idle_gaps_are_named_by_the_innermost_span_and_split():
    trace = _plain_trace()
    # the gaps between the steps' ops: 10_980 -> 11_100 (then the append
    # runs), and 11_300 -> 11_500.  The trainer's thread: a boundary
    # with two children over the first.  The server's thread: one long
    # span over both, and a short one inside the drain, which does not
    # take the drain's time: spans nest within a thread, and the
    # dispatching thread's come first.
    trace["planes"][1]["lines"] = [
        {"name": "server", "events": [
            ("hrl:learner.update", 10_900.0, 650.0),
            ("hrl:rpc.episode", 10_960.0, 20.0)]},
        {"name": "trainer", "events": [
            ("hrl:trainer.update", 10_000.0, 100.0),
            ("hrl:trainer.boundary", 10_940.0, 170.0),
            ("hrl:boundary.drain", 10_945.0, 100.0),
            ("hrl:boundary.snapshot", 11_050.0, 40.0),
            ("not ours", 10_000.0, 5000.0)]}]
    out = devtrace.idle_gaps(trace)
    assert out["window_s"] == pytest.approx(2480e-9)
    assert out["busy_share"] == pytest.approx(100 * 2160 / 2480)
    (first, second) = out["gaps"][:2]
    assert first[0] == "learner.update" and second[0] == "boundary.drain"
    assert second[1] == pytest.approx(120e-9)
    split = {k: round(v * 1e9) for k, v in second[2].items()}
    assert split == {"boundary.drain": 65, "trainer.boundary": 15,
                     "boundary.snapshot": 40}
    assert {k: round(v * 1e9) for k, v in first[2].items()} == {
        "learner.update": 200}


def test_a_gap_no_span_covers_is_untracked():
    out = devtrace.idle_gaps(_plain_trace())
    assert [g[0] for g in out["gaps"][:2]] == ["untracked", "untracked"]


def test_a_trace_without_a_device_plane_is_an_error():
    trace = _plain_trace()
    trace["planes"] = trace["planes"][1:]
    with pytest.raises(ValueError, match="no TPU device plane"):
        devtrace.step_phases(trace)
    with pytest.raises(ValueError, match="no TPU device plane"):
        devtrace.idle_gaps(trace)


def test_the_text_is_read_by_instruction_name():
    names = devtrace.op_names(HLO)
    assert names["fusion.1"] == "jit(step)/replay.gather/gather"
    assert names["add.9"] == "jit(step)/add" and names["copy.10"] == ""
    assert "mystery.11" not in names and "HloModule" not in names
    assert devtrace.instruction("%while.31 = (s32[]) while(...)") == "while.31"
    assert devtrace.instruction("ROOT %add.9 = s32[3] add(...)") == "add.9"
    assert devtrace.phase_of("jit(step)/jvp(net.forward)/while") == "forward"
    assert devtrace.phase_of(
        "jit(step)/transpose(jvp(replay.gather))/x") == "backward"
    assert devtrace.phase_of("jit(step)/optimizers/x") is None
    assert devtrace.module_name("no header") == "jit_step"
    assert "gather:0.150" in devtrace.format_phases(
        {"steps": 1, "step_ms": 1.0,
         "phases": dict.fromkeys(devtrace.PHASES, 0.15)})


# a kernel's custom call as the TPU compiler writes it (jax 0.9, libtpu
# 0.0.34; operands and the body's bytes cut): its ``kernel_metadata``
# holds newlines, so ONE instruction lies over three lines of the text,
# its ``op_name`` on the last; the tuple's elements repeat the attribute
KERNEL_HLO = """HloModule jit_step, entry_computation_layout={()}

ENTRY %main () -> f32[8] {
  %fusion.1 = bf16[2,4096,2048]{2,1,0} fusion(%a), kind=kOutput, metadata={op_name="jit(step)/jvp(net.forward)/checkpoint/layer_1/attn/net.attention.window/dot_general"}
  %splash_mqa_fwd_residuals.2 = (f32[2,4,512,128]{3,2,1,0:T(8,128)}, bf16[2,4,8,4096,128]{4,3,2,1,0:T(8,128)(2,1)}) custom-call(%copy-done.2, %fusion.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s8[1,8,5]{2,1,0}}, frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512, \\"q_layout\\": 1}"
}}, metadata={op_name="jit(step)/jvp(net.forward)/checkpoint/layer_1/attn/net.attention.window/cond/branch_0_fun/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/splash_mqa_fwd_residuals/pallas_call" stack_frame_id=8}, backend_config={"custom_call_config":{"body":"TUzvUgFNTElS"}}
  %get-tuple-element.3 = bf16[2,4,8,4096,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%splash_mqa_fwd_residuals.2), index=1, frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"block_kv\\": 512, \\"q_layout\\": 1}"
}}, metadata={op_name="jit(step)/jvp(net.forward)/checkpoint/layer_1/attn/net.attention.window/cond/branch_0_fun/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/splash_mqa_fwd_residuals/pallas_call" stack_frame_id=8}
  %copy.4 = f32[8] copy(%w)
  %splash_mqa_dq_no_residuals.5 = (f32[2,4,512,128]{3,2,1,0:T(8,128)}, bf16[2,4,8,4096,128]{4,3,2,1,0:T(8,128)(2,1)}) custom-call(%constant.8), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q_dq\\": 512, \\"block_kv_dq\\": 512}"
}}, metadata={op_name="jit(step)/transpose(jvp(net.forward))/checkpoint/layer_1/attn/net.attention.window/cond/branch_0_fun/vmap(vmap(jit(_splash_attention)))/splash_mqa_dq_no_residuals/splash_mqa_dq_no_residuals/pallas_call" stack_frame_id=8}, backend_config={"custom_call_config":{"body":"TUzvUgFN"}}
  %splash_mqa_dkv_no_residuals.6 = (f32[2,4,512,128]{3,2,1,0:T(8,128)}, bf16[2,4,4096,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(%constant.11), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q_dkv\\": 512, \\"block_kv_dkv\\": 512}"
}}, metadata={op_name="jit(step)/transpose(jvp(net.forward))/checkpoint/layer_2/attn/net.attention.full/cond/branch_0_fun/vmap(vmap(jit(_splash_attention)))/splash_mqa_dkv_no_residuals/splash_mqa_dkv_no_residuals/pallas_call" stack_frame_id=8}, backend_config={"custom_call_config":{"body":"TUzvUgFN"}}
  ROOT %fusion.7 = f32[8] fusion(%c), metadata={op_name="jit(step)/optimizer/sub"}
}
"""


def test_a_kernel_written_over_three_lines_keeps_its_op_name():
    names = devtrace.op_names(KERNEL_HLO)
    assert set(names) == {
        "fusion.1", "splash_mqa_fwd_residuals.2", "get-tuple-element.3",
        "copy.4", "splash_mqa_dq_no_residuals.5",
        "splash_mqa_dkv_no_residuals.6", "fusion.7"}
    # an instruction that has none takes none from its neighbours
    assert names["copy.4"] == ""
    forward = names["splash_mqa_fwd_residuals.2"]
    assert forward.endswith("splash_mqa_fwd_residuals/pallas_call")
    assert names["get-tuple-element.3"] == forward
    assert (devtrace.phase_of(forward), devtrace.net_scope_of(forward)) == (
        "forward", "net.attention.window")
    dq = names["splash_mqa_dq_no_residuals.5"]
    assert (devtrace.phase_of(dq), devtrace.net_scope_of(dq)) == (
        "backward", "net.attention.window")
    dkv = names["splash_mqa_dkv_no_residuals.6"]
    assert (devtrace.phase_of(dkv), devtrace.net_scope_of(dkv)) == (
        "backward", "net.attention.full")
    # as the profiler names the kernel's event: by that same text
    assert devtrace.instruction(KERNEL_HLO.split("\n  ")[2]) == \
        "splash_mqa_fwd_residuals.2"


def test_step_phases_count_a_kernels_time_for_its_scope_and_as_kernel_ms():
    t = 10_000.0
    ops = [("fusion.1", t, 100.0),
           ("splash_mqa_fwd_residuals.2", t + 100, 200.0),
           ("copy.4", t + 300, 50.0),
           ("splash_mqa_dq_no_residuals.5", t + 350, 300.0),
           ("splash_mqa_dkv_no_residuals.6", t + 650, 250.0),
           ("fusion.7", t + 900, 100.0)]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_step(1)", t, 1000.0)]},
        {"name": "XLA Ops", "events": ops}]}],
        "op_names": devtrace.op_names(KERNEL_HLO)}
    out = devtrace.step_phases(trace)
    ns = lambda d: {k: round(v * 1e6) for k, v in d.items()}  # noqa: E731
    assert ns(out["phases"]) == {
        "gather": 0, "forward": 300, "targets": 0, "backward": 550,
        "optimizer": 100, "unscoped": 50}             # copy.4 alone
    assert ns(out["scopes"]) == {"net.attention.full": 250,
                                 "net.attention.window": 600}
    assert ns(out["kernel_ms"]) == {"net.attention.full": 250,
                                    "net.attention.window": 500}
    assert out["unmatched_ms"] == 0.0
    assert "kernel[net.attention.window]:0.001" in \
        devtrace.format_phases(out)
    # a step that runs no kernel reports none
    plain = devtrace.step_phases(_plain_trace())
    assert plain["kernel_ms"] == {}
    assert "kernel" not in devtrace.format_phases(plain)


# a latent layer as PR 41 compiles it: q's pass to the attention kernel
# (norm, rotation and scale in one kernel of the module's own), the
# attention, and the pass transposed coming back; op_names as the
# compiler wrote them
_LATENT = "SequencePolicyNet/layer_1/attn/net.attention.latent"
TURN_HLO = f"""HloModule jit_step, is_scheduled=true

ENTRY %main {{
  %turn_pass.1 = bf16[1,32,8192,192]{{3,2,1,0:T(8,128)(2,1)}} custom-call(%q, %cos, %sin), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="jit(step)/jvp(net.forward)/{_LATENT}/cond/branch_0_fun/turn_pass/pallas_call" stack_frame_id=7}}
  %splash_mqa_fwd_residuals.2 = bf16[32,8192,128] custom-call(%turn_pass.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(step)/jvp(net.forward)/{_LATENT}/cond/branch_0_fun/vmap(vmap(jit(_splash_attention)))/splash_mqa_fwd_residuals/splash_mqa_fwd_residuals/pallas_call"}}
  %maximum_bitcast_fusion.3 = bf16[32,8192,192] fusion(%k), kind=kLoop, metadata={{op_name="jit(step)/jvp(net.forward)/{_LATENT}/concatenate"}}
  ROOT %turn_pass.4 = bf16[1,32,8192,192]{{3,2,1,0:T(8,128)(2,1)}} custom-call(%dq, %cos, %sin), custom_call_target="tpu_custom_call", frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="jit(step)/transpose(jvp(net.forward))/SequencePolicyNet/jvp(net.forward)/SequencePolicyNet/checkpoint/layer_1/attn/net.attention.latent/cond/branch_0_fun/turn_pass/pallas_call" stack_frame_id=7}}
}}
"""


def test_an_operands_pass_counts_as_a_kernel_of_its_layers_scope():
    names = devtrace.op_names(TURN_HLO)
    assert [(devtrace.phase_of(names[op]), devtrace.net_scope_of(names[op]))
            for op in ("turn_pass.1", "turn_pass.4")] == [
        ("forward", "net.attention.latent"),
        ("backward", "net.attention.latent")]
    t = 5_000.0
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [("jit_step(1)", t, 1000.0)]},
        {"name": "XLA Ops", "events": [
            ("turn_pass.1", t, 100.0),
            ("splash_mqa_fwd_residuals.2", t + 100, 400.0),
            ("maximum_bitcast_fusion.3", t + 500, 200.0),
            ("turn_pass.4", t + 700, 300.0)]}]}],
        "op_names": names}
    out = devtrace.step_phases(trace)
    ns = lambda d: {k: round(v * 1e6) for k, v in d.items()}  # noqa: E731
    assert ns(out["scopes"]) == {"net.attention.latent": 1000}
    # the two passes beside the attention kernel, not the XLA fusion
    assert ns(out["kernel_ms"]) == {"net.attention.latent": 800}
    assert (ns(out["phases"])["forward"], ns(out["phases"])["backward"]) == (
        700, 300)
    assert "kernel[net.attention.latent]:0.001" in \
        devtrace.format_phases(out)


# -- the ledger held to a trace (scripts/inflight_check.py) ---------------

def test_the_ledger_is_held_to_a_trace_on_one_clock():
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import inflight_check

    ms = 1e6                                     # the trace counts in ns
    # six steps of 3 ms; after the third the device waits 12 ms (an
    # ingest call outlasted the queue), after the fifth 0.5 ms
    starts = [0.0, 3.0, 6.0, 21.0, 24.0, 27.5]
    trace = {"module": "jit_step", "op_names": {}, "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_step(1)", 1e9 + t * ms, 3.0 * ms) for t in starts]
             + [("jit_append(2)", 1e9 + 16.0 * ms, 0.2 * ms)]}]},
        {"name": "/host:CPU", "lines": [{"name": "trainer", "events": [
            ("hrl:trainer.update", 1e9 + t * ms, 0.4 * ms)
            for t in (-3.0, 0.1, 2.0, 19.5, 22.0, 26.9)]
            + [("hrl:trainer.ingest", 1e9 + 4.0 * ms, 15.0 * ms),
               ("hrl:ingest.decompress", 1e9 + 4.0 * ms, 12.0 * ms),
               ("hrl:ingest.append", 1e9 + 16.5 * ms, 2.0 * ms)]}]}]}
    off = 500.0 - 1.0            # the telemetry clock reads 500 s at 1e9 ns

    def rec(name, at_ms, dur_ms, **attrs):
        out = {"name": name, "ts": 1.0 + off + at_ms * 1e-3,
               "dur": dur_ms * 1e-3, "pid": 1, "tid": 7}
        if attrs:
            out["attrs"] = attrs
        return out

    records = [rec("trainer.update", t, 0.4, depth=1, done=0)
               for t in (-9.0, -6.0, -3.0, 0.1, 2.0, 19.5, 22.0, 26.9, 30.0)]
    records += [rec("trainer.ingest", 4.0, 15.0, episodes=3),
                rec("ingest.decompress", 4.0, 12.0),
                rec("ingest.append", 16.5, 2.0, depth=0, done=0),
                # found dry at 10 ms, a poll after the one at 8: the
                # device ran dry at 9; the launch returned at 19.9, the
                # device had started at 21 by the trace's own latency
                rec("device.starved", 10.0, 9.9, since_ms=2.0,
                    at="ingest.decompress"),
                # a dispatch that found the queue dry as it returned
                rec("device.starved", 27.3, 0.0, since_ms=0.4, at="update")]
    out = inflight_check.compare(trace, records, gap_ms=10.0)
    assert out["clock_offset_s"] == pytest.approx(off)
    assert out["steps"] == 6 and out["stretch_s"] == pytest.approx(30.5e-3)
    assert out["trace_idle_s"] == pytest.approx(12.5e-3)
    assert out["trace_idle_back_to_back_s"] == 0.0
    assert out["ledger_spans_s"] == pytest.approx(9.9e-3)
    assert out["ledger_upper_s"] == pytest.approx(12.3e-3)
    # less the 0.4 ms of the stretch inside the dispatch that closed it
    assert out["ledger_lower_s"] == pytest.approx(9.5e-3)
    assert out["bracket_points"] == pytest.approx(100 * 2.8 / 30.5, abs=1e-3)
    # 12.5 ms of idle lie 0.2 ms above the bracket: after the launch's
    # return the device still takes the dispatch's hand-over, which no
    # poll sees, and the script says so as it is
    assert out["inside_bracket"] is False
    assert (out["long_gaps"], out["long_gaps_covered"],
            out["long_gaps_named_alike"]) == (1, 1, 1)
    (gap,) = out["gaps"]
    assert gap["gap_ms"] == pytest.approx(12.0)
    assert gap["trace_names"] == gap["ledger_names"] == "ingest.decompress"
    assert gap["found_at"] == ["ingest.decompress"]
    # a log whose dispatches the trace's do not follow is refused
    with pytest.raises(ValueError, match="no alignment"):
        inflight_check.compare(
            trace, [dict(r, ts=r["ts"] + 0.005 * k)
                    for k, r in enumerate(records)])


# -- Trainer.step_profile -------------------------------------------------

def test_step_profile_is_none_not_an_exception_without_a_tpu(
        tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", None)
    from test_durability import _train_args

    from handyrl_tpu.learner import Trainer

    model, _, episodes = _ttt(12)
    args = dict(_train_args(extra_train={
        "mesh": {"dp": 1}, "device_replay": "on",
        "telemetry": False})["train_args"], env={"env": "TicTacToe"},
        restart_epoch=0)
    from handyrl_tpu.config import Config

    full = Config.from_dict({"env_args": {"env": "TicTacToe"},
                             "train_args": {k: v for k, v in args.items()
                                            if k != "env"}})
    train = full.train_args.to_dict()
    train["env"] = {"env": "TicTacToe"}
    trainer = Trainer(train, model)
    # before the ring holds anything: no fused step to run
    assert trainer.step_profile() is None
    assert "step profile not taken" in capsys.readouterr().out
    trainer._step_profile = None
    trainer.device_replay.offer(episodes)
    trainer.device_replay.ingest()
    steps = trainer.steps
    assert trainer.step_profile(steps=2) is None      # a CPU: no TPU plane
    out = capsys.readouterr().out
    assert out.count("step profile not taken") == 1
    assert "no TPU device plane" in out
    assert trainer.steps == steps + 2                 # the steps did run
    assert trainer.step_profile() is None             # cached: no second capture
    assert "step profile" not in capsys.readouterr().out
    assert not [d for d in os.listdir(tmp_path)
                if d.startswith("hrl-step-profile-")]
    # what a chip's trace would be reduced to also says how the step's
    # value targets were scheduled, from the step's own compile record
    from handyrl_tpu.telemetry import devtrace

    phases = {"steps": 2, "step_ms": 1.0, "scopes": {}, "kernel_ms": {},
              "phases": dict.fromkeys(devtrace.PHASES, 0.0)}
    trainer._step_profile = None
    with monkeypatch.context() as patched:
        patched.setattr(devtrace, "load", lambda path, hlo: {})
        patched.setattr(devtrace, "step_phases", lambda trace: dict(phases))
        assert trainer.step_profile(steps=2)["targets_scan"] == {
            "form": "sequential", "length": train["forward_steps"] - 1}
    capsys.readouterr()
    # from another thread while the trainer's own runs: refused
    trainer._step_profile = None
    trainer._run_thread = threading.Thread(target=lambda: None)
    assert trainer.step_profile() is None
    assert "the trainer thread is running" in capsys.readouterr().out
    trainer.shutdown()
