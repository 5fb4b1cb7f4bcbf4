"""The epoch's metric sums ride the fused replay step (PR 30): the step
adds its ``metrics`` to sums carried in its ``state``, the trainer
keeps them across the ring's re-uploads, and the boundary fetches them
once — the same numbers ``_finish_epoch`` had from a per-step list
summed on the host, by a fetch that does not grow with the steps."""

import logging
import math

import numpy as np
import pytest

from handyrl_tpu import telemetry


def _trainer(tmp_path, monkeypatch, episodes=40, **extra):
    """A Trainer on a TicTacToe ring primed with 12 episodes, driven on
    the calling thread; the rest of the episodes for feeding mid-epoch."""
    monkeypatch.chdir(tmp_path)
    from test_durability import _train_args
    from test_layer_spans import _ttt

    from handyrl_tpu.config import Config
    from handyrl_tpu.learner import Trainer

    model, _, eps = _ttt(episodes)
    train = dict(_train_args(extra_train={
        "device_replay": "on", "telemetry": False, **extra})["train_args"],
        restart_epoch=0)
    train = Config.from_dict({"env_args": {"env": "TicTacToe"},
                              "train_args": train}).train_args.to_dict()
    train["env"] = {"env": "TicTacToe"}
    trainer = Trainer(train, model)
    trainer.device_replay.offer(eps[:12])
    trainer.device_replay.ingest()
    return trainer, eps[12:]


def _epoch(trainer, steps, feed=None, poison=()):
    """One epoch of ``steps`` fused steps through ``Trainer.train``.
    ``feed[k]`` is offered to the ring after the k-th step, so the next
    ingest moves the ring and its half of ``state`` is uploaded anew;
    the steps numbered in ``poison`` run on NaN parameters (the true
    ones are put back after).  Returns every step's own ``metrics`` as
    host numbers: what the boundary used to sum."""
    import jax
    import jax.numpy as jnp

    real, seen = trainer._replay_step, []

    def step(params, *rest):
        kept = None
        if len(seen) + 1 in poison:
            kept = jax.device_get((params, rest[0]))
            params = jax.tree.map(lambda x: x * np.nan, params)
        out = real(params, *rest)
        if kept is not None:
            out = tuple(jax.tree.map(jnp.asarray, kept)) + tuple(out[2:])
        seen.append(jax.device_get(out[2]))
        if feed and len(seen) in feed:
            trainer.device_replay.offer(feed[len(seen)])
        if len(seen) >= steps:
            trainer.update_flag = True
        return out

    trainer._replay_step, trainer.update_flag = step, False
    try:
        assert trainer.train() is not None
    finally:
        trainer._replay_step = real
    assert len(seen) == steps
    return seen


def _from_the_host(trainer, seen, ema, steps_before):
    """What ``_finish_epoch`` made of the per-step list before the sums
    moved onto the device (the parent's arithmetic, in float64)."""
    data_cnt = sum(float(m["dcnt"]) for m in seen)
    losses = {k: sum(float(m[k]) for m in seen) / data_cnt
              for k in ("p", "v", "r", "ent", "total") if k in seen[0]}
    # the most the float32 running sum on the device may differ by:
    # 1e-5 of the size of what was summed
    room = {k: 1e-5 * sum(abs(float(m[k])) for m in seen) / data_cnt
            for k in losses}
    ema = ema * 0.8 + data_cnt / (1e-2 + len(seen)) * 0.2
    lr = trainer.default_lr * ema / (
        1 + (steps_before + len(seen)) * 1e-5)
    clip = sum(float(m["clip_frac"]) for m in seen) / len(seen)
    nonfinite = sum(float(m["nonfinite"]) >= 0.5 for m in seen)
    return losses, room, ema, np.float32(lr), clip, nonfinite


@pytest.mark.parametrize("algorithm", ["standard", "impact"])
def test_the_carried_sums_read_as_the_host_sums_across_ring_appends(
        tmp_path, monkeypatch, algorithm):
    extra = {"mesh": {"dp": 1}}
    if algorithm == "impact":
        extra.update(update_algorithm="impact", target_update_interval=4)
    trainer, rest = _trainer(tmp_path, monkeypatch, **extra)
    assert (trainer.target_params is not None) == (algorithm == "impact")
    replay = trainer.device_replay
    uploads = []
    real_state = replay.device_state
    replay.device_state = lambda idx: uploads.append(idx) or real_state(idx)
    telemetry.configure(enabled=True)

    ema, before = trainer.data_cnt_ema, trainer.steps
    seen = _epoch(trainer, 12, feed={3: rest[:5], 7: rest[5:9]})
    # the ring moved twice inside the epoch: its half went up anew
    # each time (at the steps it was then at), the sums stayed
    assert uploads == [before, before + 3, before + 7]
    assert replay.episodes_seen == 12 + 9
    losses, room, ema, lr, clip, nonfinite = _from_the_host(
        trainer, seen, ema, before)
    last = trainer.last_metrics
    for key, value in losses.items():
        assert last[key] == pytest.approx(value, rel=0, abs=room[key]), key
    assert set(losses) == {"p", "v", "ent", "total"}     # no return head
    assert "r" not in last
    # counts sum exactly: the learning rate hangs on dcnt's
    assert trainer.data_cnt_ema == ema
    assert np.float32(
        trainer.opt_state.hyperparams["learning_rate"]) == lr
    assert last["nonfinite_steps"] == nonfinite == 0
    assert last["is_clip_frac"] == pytest.approx(clip, abs=1e-4)
    (drain,) = [r for r in telemetry.ring_snapshot()
                if r["name"] == "boundary.drain"]
    assert drain["attrs"]["steps"] == 12

    # the second epoch starts from zeros: its numbers are its own steps'
    before = trainer.steps
    seen = _epoch(trainer, 5)
    assert uploads[3:] == [before]
    losses, room, ema, lr, _, _ = _from_the_host(trainer, seen, ema, before)
    for key, value in losses.items():
        assert trainer.last_metrics[key] == pytest.approx(
            value, rel=0, abs=room[key]), key
    assert trainer.data_cnt_ema == ema
    assert np.float32(
        trainer.opt_state.hyperparams["learning_rate"]) == lr
    drains = [r["attrs"] for r in telemetry.ring_snapshot()
              if r["name"] == "boundary.drain"]
    assert [d["steps"] for d in drains] == [12, 5]
    trainer.shutdown()


def test_the_counts_sum_in_int32_and_the_zeros_come_from_the_host(
        tmp_path, monkeypatch):
    import jax

    from handyrl_tpu.staging import COUNT_SUMS, LOSS_SUMS, epoch_sums

    trainer, _ = _trainer(tmp_path, monkeypatch, mesh={"dp": 1})
    with _lowerings() as lowered:
        sums = epoch_sums(trainer.device_replay)
    assert lowered == []                    # host zeros, put: no program
    assert set(sums) == set(LOSS_SUMS + COUNT_SUMS + ("steps",))
    assert all(not np.any(jax.device_get(v)) for v in sums.values())
    assert {k: str(v.dtype) for k, v in sums.items()} == {
        **dict.fromkeys(LOSS_SUMS, "float32"),
        **dict.fromkeys(COUNT_SUMS + ("steps",), "int32")}
    trainer.shutdown()


def test_the_drain_fetches_the_same_few_arrays_whatever_the_steps(
        tmp_path, monkeypatch):
    trainer, _ = _trainer(tmp_path, monkeypatch, mesh={"dp": 1})
    telemetry.configure(enabled=True)
    _epoch(trainer, 5)
    _epoch(trainer, 50)
    short, long_ = [r["attrs"] for r in telemetry.ring_snapshot()
                    if r["name"] == "boundary.drain"]
    assert (short["steps"], long_["steps"]) == (5, 50)
    assert short["arrays"] == long_["arrays"] < 16
    trainer.shutdown()


class _lowerings:
    """The programs JAX says it compiles, as the benchmark counts a
    window's (``benchmarks/run.py``): one message per lowering, a
    cache hit by another committedness or sharding included."""

    def __enter__(self):
        seen = self.seen = []

        class Keep(logging.Handler):
            def emit(self, record):
                message = record.getMessage()
                if message.startswith("Compiling "):
                    seen.append(message[:80])

        self.logger = logging.getLogger("jax._src.interpreters.pxla")
        self.handler = Keep(level=logging.DEBUG)
        self.was = self.logger.level, self.logger.propagate
        self.logger.addHandler(self.handler)
        self.logger.setLevel(logging.DEBUG)
        self.logger.propagate = False
        return seen

    def __exit__(self, *exc):
        self.logger.removeHandler(self.handler)
        self.logger.setLevel(self.was[0])
        self.logger.propagate = self.was[1]
        return False


@pytest.mark.parametrize("mesh", [{"dp": 1}, {"dp": 4}],
                         ids=["one_device", "dp4"])
def test_the_reset_lowers_nothing_across_three_epochs(
        tmp_path, monkeypatch, mesh):
    trainer, rest = _trainer(tmp_path, monkeypatch, mesh=mesh)
    assert (trainer.train_mesh is not None) == (mesh["dp"] > 1)
    # the first epoch holds every form the step's arguments take: the
    # run's first call, a threaded state, the ring's half made anew
    with _lowerings() as lowered:
        _epoch(trainer, 6, feed={2: rest[:4]})
    assert len(lowered) == 1 and "jit(step)" in lowered[0], lowered
    compiles = trainer.retrace_guard.compiles
    assert compiles == 1
    with _lowerings() as lowered:
        _epoch(trainer, 6, feed={2: rest[4:8]})
        _epoch(trainer, 6, feed={2: rest[8:12]})
        _epoch(trainer, 6)
    # zeros made as the run's first were, put and not computed: no
    # epoch after the first lowers anything, the step least of all
    assert lowered == []
    assert trainer.retrace_guard.compiles == compiles
    assert trainer.last_metrics["retrace_count"] == compiles
    # ... and the one lowering recorded how the value targets' recursion
    # was scheduled and at what length of the time axis (ops/targets.py)
    scan = {"form": "sequential",
            "length": trainer.args["forward_steps"] - 1}
    assert trainer.targets_scan == scan
    assert trainer.last_metrics["targets_scan"] == scan
    trainer.shutdown()


def test_a_nan_step_counts_once_and_poisons_its_own_epoch_alone(
        tmp_path, monkeypatch):
    from handyrl_tpu.analysis.guards import NumericsError

    trainer, _ = _trainer(tmp_path, monkeypatch, mesh={"dp": 1},
                          max_nonfinite_steps=1)
    seen = _epoch(trainer, 6, poison={3})
    assert [float(m["nonfinite"]) for m in seen] == [0, 0, 1, 0, 0, 0]
    last = trainer.last_metrics
    assert last["nonfinite_steps"] == 1          # at the budget: counted
    assert math.isnan(last["total"])             # as the host's sum was
    ema = trainer.data_cnt_ema
    assert math.isfinite(ema)                    # dcnt is a mask's sum

    seen = _epoch(trainer, 4)                    # the reset took the NaN
    last = trainer.last_metrics
    assert last["nonfinite_steps"] == 0
    assert all(math.isfinite(last[k]) for k in ("p", "v", "ent", "total"))
    assert trainer.num_guard.nonfinite_steps == 1

    with pytest.raises(NumericsError, match=r"2 nonfinite update steps "
                                            r"\(budget 1\)"):
        _epoch(trainer, 4, poison={2})           # over it: raised
    trainer.shutdown()


@pytest.mark.parametrize("keys", [
    ("p", "v", "ent", "total", "clip_frac", "dcnt", "nonfinite"),
    ("p", "v", "r", "ent", "total", "clip_frac", "dcnt", "nonfinite",
     "anakin_frames", "anakin_games"),
], ids=["host_batch", "anakin"])
def test_the_other_loops_lists_reduce_to_the_same_form(keys):
    """The host-batch, multi-host and Anakin loops keep their per-step
    lists; one helper sums them to what the fused step carries."""
    from handyrl_tpu.learner import _sum_steps

    rng = np.random.default_rng(5)
    per_step = [{k: np.float32(rng.integers(0, 9)) for k in keys}
                | {"grad_norm": np.float32(1.5)} for _ in range(7)]
    per_step[2]["nonfinite"] = np.float32(1.0)
    sums = _sum_steps(per_step)
    assert set(sums) == set(keys) | {"steps"}        # grad_norm: unread
    assert sums["steps"] == 7
    for key in keys:
        assert sums[key] == sum(float(m[key]) for m in per_step)
    assert _sum_steps([]) == {"steps": 0}
