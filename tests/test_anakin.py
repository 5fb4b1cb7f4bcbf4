"""Anakin mode: the pure-JAX env's exhaustive parity proof, the fused
rollout engine's batch semantics, and the end-to-end learner wiring.

The Python env (envs/tictactoe.py) is the SPEC: the parity test walks
EVERY reachable tictactoe position in lockstep between the two
implementations and asserts transitions, rewards, terminal flags,
legal masks, observations, and outcomes bit-match — any divergence is
a bug in the JAX port, never a new convention.
"""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from handyrl_tpu.anakin import AnakinConfig, AnakinEngine  # noqa: E402
from handyrl_tpu.environment import (  # noqa: E402
    jax_env_available,
    make_env,
    make_jax_env,
)
from handyrl_tpu.envs import tictactoe as pyttt  # noqa: E402
from handyrl_tpu.envs import tictactoe_jax as jxttt  # noqa: E402
from handyrl_tpu.models import TPUModel  # noqa: E402
from handyrl_tpu.ops.losses import LossConfig  # noqa: E402
from handyrl_tpu.ops.update import make_optimizer  # noqa: E402

TTT_CFG = {
    "turn_based_training": True, "observation": False, "gamma": 0.8,
    "forward_steps": 8, "burn_in_steps": 0, "compress_steps": 4,
    "entropy_regularization": 0.05,
    "entropy_regularization_decay": 0.1,
    "lambda": 0.7, "policy_target": "TD", "value_target": "TD",
}

# every reachable tictactoe position, including terminal ones — the
# classic enumeration result the exhaustive walk must reproduce (a
# mismatch means the breadth-first expansion itself diverged)
REACHABLE_POSITIONS = 5478


def _clone(env):
    e = pyttt.Environment()
    e.cells = env.cells.copy()
    e.side_to_move = env.side_to_move
    e.winner = env.winner
    e.history = list(env.history)
    return e


def _state_stack(states):
    """List of single States -> one batched State."""
    return jxttt.State(
        cells=jnp.stack([s.cells for s in states]),
        count=jnp.stack([s.count for s in states]),
        winner=jnp.stack([s.winner for s in states]),
    )


def _state_row(states, i):
    return jax.tree.map(lambda a: a[i], states)


def test_jax_env_bit_matches_python_env_exhaustively():
    """Walk the FULL reachable state space breadth-first, the Python
    env expanding the spec side and ``vmap(step)`` expanding the JAX
    side from the very states it produced — so the port is proven over
    every transition, not a sampled subset."""
    step_v = jax.jit(jax.vmap(jxttt.step))
    key0 = jax.random.PRNGKey(0)

    root = pyttt.Environment()
    envs = [root]
    states = _state_stack([jxttt.init(key0)])
    total = 0

    for _depth in range(10):
        if not envs:
            break
        total += len(envs)
        cells = np.asarray(states.cells)
        counts = np.asarray(states.count)
        terms = np.asarray(jax.vmap(jxttt.terminal)(states))
        legals = np.asarray(jax.vmap(jxttt.legal_mask)(states))
        turns = np.asarray(jax.vmap(jxttt.turn)(states))
        obs = np.asarray(jax.vmap(jxttt.observe)(states))
        outcomes = np.asarray(jax.vmap(jxttt.outcome)(states))
        for i, e in enumerate(envs):
            assert np.array_equal(cells[i], e.cells)
            assert counts[i] == len(e.history)
            assert bool(terms[i]) == e.terminal()
            assert (sorted(np.flatnonzero(legals[i]).tolist())
                    == sorted(e.legal_actions()))
            # the acting view (player=None == the turn player's view)
            assert np.array_equal(obs[i], e.observation(None))
            if not e.terminal():
                assert int(turns[i]) == e.turn()
            else:
                oc = e.outcome()
                assert outcomes[i][0] == oc[0]
                assert outcomes[i][1] == oc[1]

        # expand every legal action of every non-terminal state
        pair_idx, pair_act, children = [], [], []
        for i, e in enumerate(envs):
            if e.terminal():
                continue
            for a in e.legal_actions():
                child = _clone(e)
                child.play(a)
                pair_idx.append(i)
                pair_act.append(a)
                children.append(child)
        if not children:
            envs, states = [], None
            break
        parents = jax.tree.map(
            lambda arr: arr[np.asarray(pair_idx)], states)
        keys = jax.random.split(key0, len(children))
        new_states, step_obs, rewards, dones, step_legals = step_v(
            parents, jnp.asarray(pair_act, jnp.int32), keys)
        step_obs = np.asarray(step_obs)
        rewards = np.asarray(rewards)
        dones = np.asarray(dones)
        step_legals = np.asarray(step_legals)
        # per-transition step() contract vs the child the spec produced
        seen, keep, next_envs = {}, [], []
        for j, child in enumerate(children):
            assert bool(dones[j]) == child.terminal()
            assert np.array_equal(step_obs[j], child.observation(None))
            assert (sorted(np.flatnonzero(step_legals[j]).tolist())
                    == sorted(child.legal_actions()))
            if child.terminal():
                oc = child.outcome()
                assert rewards[j][0] == oc[0] and rewards[j][1] == oc[1]
            else:
                assert rewards[j][0] == 0.0 and rewards[j][1] == 0.0
            board = child.cells.tobytes()
            if board not in seen:
                seen[board] = j
                keep.append(j)
                next_envs.append(child)
        states = jax.tree.map(
            lambda arr: arr[np.asarray(keep)], new_states)
        envs = next_envs

    assert total == REACHABLE_POSITIONS


def test_jax_env_hardenings_are_inert():
    """The vmapped fleet's extra contract: stepping a terminal state or
    an occupied cell is a NO-OP (the Python spec is never driven with
    either, so this is the port's only permitted extension)."""
    key = jax.random.PRNGKey(0)
    s = jxttt.init(key)
    s, _, _, _, _ = jxttt.step(s, jnp.int32(4), key)
    before = jax.tree.map(np.asarray, s)
    s2, _, _, _, _ = jxttt.step(s, jnp.int32(4), key)  # occupied
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(s2)):
        assert np.array_equal(a, np.asarray(b))
    # drive to a win, then step again
    term = jxttt.from_board([1, 1, 1, -1, -1, 0, 0, 0, 0])
    assert bool(jxttt.terminal(term))
    t2, _, rew, done, _ = jxttt.step(term, jnp.int32(5), key)
    assert bool(done)
    assert float(rew[0]) == 0.0  # no re-delivered reward
    for a, b in zip(jax.tree.leaves(term), jax.tree.leaves(t2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_registry_exposes_the_jax_twin():
    assert jax_env_available({"env": "TicTacToe"})
    assert not jax_env_available({"env": "HungryGeese"})
    assert make_jax_env({"env": "TicTacToe"}) is jxttt
    with pytest.raises(ValueError):
        make_jax_env({"env": "HungryGeese"})


def test_anakin_config_validation():
    assert not AnakinConfig.from_config({}).enabled
    cfg = AnakinConfig.from_config(
        {"mode": "on", "num_envs": 64, "opponent_pool": 3})
    assert cfg.enabled and cfg.num_envs == 64
    with pytest.raises(ValueError):
        AnakinConfig.from_config({"mode": "sometimes"})
    with pytest.raises(ValueError):
        AnakinConfig.from_config({"mode": "on", "num_envs": 0})
    with pytest.raises(ValueError):
        AnakinConfig.from_config({"nope": 1})
    with pytest.raises(ValueError):
        # 64 games cannot split into 3 equal opponent groups
        AnakinConfig.from_config(
            {"mode": "on", "num_envs": 64, "opponent_pool": 2})


def test_anakin_requires_step_driven_epochs():
    """Config cross-check: anakin without updates_per_epoch can never
    finish an epoch (nothing ticks episode intake)."""
    from handyrl_tpu.config import Config

    base = {
        "env_args": {"env": "TicTacToe"},
        "train_args": {"anakin": {"mode": "on"},
                       "updates_per_epoch": 0},
    }
    with pytest.raises(ValueError, match="updates_per_epoch"):
        Config.from_dict(base)
    base["train_args"]["updates_per_epoch"] = 10
    Config.from_dict(base)  # valid


def _engine(num_envs=64, opponent_pool=0, seed=0, cfg_over=None,
            **engine_kw):
    cfg = dict(TTT_CFG, **(cfg_over or {}))
    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=seed)
    loss_cfg = LossConfig.from_config(cfg)
    optimizer = make_optimizer(1e-3)
    acfg = AnakinConfig.from_config({
        "mode": "on", "num_envs": num_envs,
        "opponent_pool": opponent_pool})
    engine = AnakinEngine(
        make_jax_env({"env": "TicTacToe"}), model, loss_cfg,
        optimizer, acfg, seed=seed, **engine_kw)
    params = jax.tree.map(jnp.asarray, model.params)
    return engine, params, optimizer


def test_rollout_batch_matches_make_batch_semantics():
    """Each env row is one complete episode in the turn-based batch
    layout: exactly one acting seat per committed step, make_batch's
    padding values on the tail (outcome-bootstrapped values, prob 1.0,
    all-illegal masks, progress 1.0), zero-sum outcomes."""
    engine, params, _ = _engine(num_envs=64)
    batch, carry2, frames = jax.jit(engine._rollout)(
        params, (), engine.init_carry(0))
    b = jax.device_get(batch)
    em = b["episode_mask"][..., 0, 0]                       # (N, T)
    tm = b["turn_mask"]                                     # (N,T,P,1)
    lens = em.sum(axis=1)
    assert int(frames) == int(em.sum())
    # one acting seat per committed step, none on padding
    assert np.array_equal(tm.sum(axis=2)[..., 0], em)
    assert np.array_equal(tm, b["observation_mask"])
    # tictactoe episodes run 5..9 moves and strictly alternate seats
    assert lens.min() >= 5 and lens.max() <= 9
    seat_idx = tm.argmax(axis=2)[..., 0]
    for g in range(len(lens)):
        L = int(lens[g])
        assert np.array_equal(seat_idx[g, :L], np.arange(L) % 2)
        assert em[g, :L].all() and not em[g, L:].any()
    oc = b["outcome"][:, 0, :, 0]
    assert set(np.unique(oc)) <= {-1.0, 0.0, 1.0}
    assert np.allclose(oc.sum(axis=1), 0.0)
    # the padded tail bootstraps every seat with the final outcome
    # (the host path's np.tile(outcome) padding) and closes the masks
    for g in range(len(lens)):
        L = int(lens[g])
        if L < engine.unroll:
            assert np.allclose(b["value"][g, L:, :, 0], oc[g][None, :])
            assert (b["selected_prob"][g, L:] == 1.0).all()
            assert (b["action_mask"][g, L:] >= 1e31).all()
            assert (b["progress"][g, L:] == 1.0).all()
    # behavior probs are genuine probabilities; progress is t/len
    assert (b["selected_prob"] > 0).all()
    assert (b["selected_prob"] <= 1).all()
    g0_len = int(lens[0])
    assert np.allclose(
        b["progress"][0, :g0_len, 0],
        np.arange(g0_len) / g0_len)


def test_rollout_is_deterministic_and_carry_advances_the_stream():
    engine, params, _ = _engine(num_envs=32)
    roll = jax.jit(engine._rollout)
    b1, c1, f1 = roll(params, (), engine.init_carry(0))
    b2, c2, f2 = roll(params, (), engine.init_carry(0))
    for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(b2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the returned carry drives a DIFFERENT segment
    b3, _, _ = roll(params, (), c1)
    assert not all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(b3)))


def test_opponent_pool_policies_actually_act():
    """Wire proof for the opponent axis: freeze a ZERO net (uniform
    policy) into the pool — on pool-group games every opponent-seat
    move must record the uniform probability 1/(empty cells), while
    learner-seat moves keep the live net's non-uniform policy."""
    engine, params, _ = _engine(num_envs=32, opponent_pool=1)
    pool = jax.tree.map(
        lambda a: jnp.zeros((1,) + a.shape, a.dtype), params)
    batch, _, _ = jax.jit(engine._rollout)(
        params, pool, engine.init_carry(0))
    b = jax.device_get(batch)
    em = b["episode_mask"][..., 0, 0]
    seat = b["turn_mask"].argmax(axis=2)[..., 0]            # (N, T)
    prob = b["selected_prob"][..., 0, 0]                    # (N, T)
    # seg 0: learner seat of game g is g % 2; groups split [self, pool]
    group = engine.group
    uniform_hits = nonuniform = 0
    for g in range(group, engine.num_envs):
        for t in range(int(em[g].sum())):
            expect_uniform = seat[g, t] != (g % 2)
            u = 1.0 / (9 - t)  # tictactoe: 9-t empty cells at step t
            if expect_uniform:
                assert abs(prob[g, t] - u) < 1e-5, (g, t, prob[g, t])
                uniform_hits += 1
            elif abs(prob[g, t] - u) > 1e-4:
                nonuniform += 1
    assert uniform_hits > 50          # the pool really played
    assert nonuniform > 10            # and the live net really played
    # self-play group: both seats the live net — uniform only by luck
    assert any(
        abs(prob[g, t] - 1.0 / (9 - t)) > 1e-4
        for g in range(group) for t in range(int(em[g].sum())))


def test_refresh_pool_shifts_newest_in_oldest_out():
    engine, params, _ = _engine(num_envs=30, opponent_pool=2)
    mark = jax.tree.map(lambda a: jnp.full_like(a, 7.0), params)
    pool = engine.init_pool(mark)
    newest = jax.tree.map(lambda a: jnp.full_like(a, 1.0), params)
    pool = engine.refresh_pool(pool, newest)
    leaf = jax.tree.leaves(pool)[0]
    assert np.allclose(np.asarray(leaf)[0], 1.0)   # newest in slot 0
    assert np.allclose(np.asarray(leaf)[1], 7.0)   # history shifted
    assert leaf.shape[0] == 2


def test_fused_step_compiles_once_and_keeps_layouts():
    """The acceptance contract of the fused step: N fused steps =
    exactly 1 compile (RetraceGuard) and 0 resharding copies
    (ShardingContractGuard) with donated state threading through."""
    from handyrl_tpu.analysis.guards import (
        RetraceGuard,
        ShardingContractGuard,
    )

    engine, params, optimizer = _engine(num_envs=32)
    retrace = RetraceGuard(max_compiles=1, name="anakin_step")
    shard = ShardingContractGuard(max_copies=0, name="anakin_step")
    step = retrace.wrap(shard.wrap(engine.make_fused_step()))
    opt_state = optimizer.init(params)
    carry = engine.init_carry(0)
    for _ in range(5):
        params, opt_state, metrics, carry = step(
            params, opt_state, carry, ())
    m = jax.device_get(metrics)
    assert np.isfinite(float(m["total"]))
    assert int(m["anakin_games"]) == 32
    assert 5 * 32 <= int(m["anakin_frames"]) <= 9 * 32
    assert retrace.compiles == 1
    assert shard.copies == 0


def test_fused_step_runs_tp_fsdp_mesh_with_sharded_pool():
    """Mesh-general Anakin (GSPMD inference plane tentpole): the fused
    step runs on a dp4 x tp2 + fsdp mesh — not just replicated-params
    dp — with the opponent pool laid out EXACTLY like the params it
    stacks (a replicated pool would keep K full weight copies per
    device and defeat fsdp), the same 1-compile/0-reshard guard
    contract, and a refresh that keeps the pool layout."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    from handyrl_tpu.analysis.guards import (
        RetraceGuard,
        ShardingContractGuard,
    )
    from handyrl_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=4, tp=2), devices=jax.devices()[:8])
    engine, params, optimizer = _engine(
        num_envs=32, opponent_pool=1, mesh=mesh, fsdp=True)
    params = jax.device_put(params, engine._p_shard)
    opt_state = jax.jit(optimizer.init,
                        out_shardings=engine._o_shard)(params)
    pool = engine.init_pool(params)
    # the fsdp rule reached the pool THROUGH its stack axis: some leaf
    # shards a trailing dim over dp while the leading pool axis stays
    # replicated
    pool_specs = [tuple(l.sharding.spec) for l in jax.tree.leaves(pool)]
    assert any("dp" in s for s in pool_specs), \
        "pool leaves are replicated — the param layout never applied"
    assert all(not s or s[0] is None for s in pool_specs), \
        "the pool's stack axis must stay replicated"

    retrace = RetraceGuard(max_compiles=1, name="anakin_mesh_step")
    shard = ShardingContractGuard(max_copies=0, name="anakin_mesh_step")
    step = retrace.wrap(shard.wrap(engine.make_fused_step()))
    carry = engine.init_carry(0)
    for _ in range(3):
        params, opt_state, metrics, carry = step(
            params, opt_state, carry, pool)
    m = jax.device_get(metrics)
    assert np.isfinite(float(m["total"]))
    assert retrace.compiles == 1
    assert shard.copies == 0
    # params came back on their tp/fsdp layout (donation-compatible)
    assert any("dp" in tuple(l.sharding.spec) or "tp" in
               tuple(l.sharding.spec) for l in jax.tree.leaves(params))
    # the epoch-boundary refresh keeps the pool layout, so the NEXT
    # fused step sees the contract it compiled with
    refreshed = engine.refresh_pool(pool, params)
    assert [tuple(l.sharding.spec)
            for l in jax.tree.leaves(refreshed)] == pool_specs
    params, opt_state, metrics, carry = step(
        params, opt_state, carry, refreshed)
    assert retrace.compiles == 1 and shard.copies == 0


def test_engine_layout_validation():
    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(0), seed=0)
    optimizer = make_optimizer(1e-3)
    jxenv = make_jax_env({"env": "TicTacToe"})
    ok = AnakinConfig.from_config({"mode": "on", "num_envs": 8})
    with pytest.raises(ValueError, match="turn_based_training"):
        AnakinEngine(jxenv, model,
                     LossConfig.from_config(
                         dict(TTT_CFG, turn_based_training=False)),
                     optimizer, ok)
    with pytest.raises(ValueError, match="burn_in"):
        AnakinEngine(jxenv, model,
                     LossConfig.from_config(
                         dict(TTT_CFG, burn_in_steps=2)),
                     optimizer, ok)
    with pytest.raises(ValueError, match="episode-aligned"):
        AnakinEngine(jxenv, model, LossConfig.from_config(TTT_CFG),
                     optimizer, AnakinConfig.from_config(
                         {"mode": "on", "num_envs": 8,
                          "unroll_length": 4}))


def test_trainer_falls_back_without_a_jax_twin(tmp_path, monkeypatch):
    """anakin.mode: auto on an env with no JAX twin keeps the IMPALA
    path (device replay et al.); mode: on raises."""
    monkeypatch.chdir(tmp_path)
    from handyrl_tpu.learner import Trainer

    base = dict(
        TTT_CFG, env={"env": "HungryGeese"}, batch_size=16,
        minimum_episodes=4, maximum_episodes=64, num_batchers=1,
        update_episodes=8, eval_rate=0.1, seed=0, restart_epoch=0,
        updates_per_epoch=4, epochs=1, observation=False,
        turn_based_training=False, device_replay="off",
        telemetry=False,
        anakin={"mode": "auto", "num_envs": 8},
    )
    env = make_env({"env": "HungryGeese"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=0)
    trainer = Trainer(base, model)
    assert trainer.anakin is None       # fell back
    assert trainer.batcher is not None  # IMPALA path intact
    trainer.shutdown()

    base["anakin"] = {"mode": "on", "num_envs": 8}
    with pytest.raises(ValueError, match="pure-JAX twin"):
        Trainer(base, model)


def test_trainer_auto_falls_back_on_layout_constraints(
        tmp_path, monkeypatch):
    """anakin.mode: auto with a JAX twin but an unsupported batch
    layout (observation: true here) keeps the IMPALA path; mode: on
    raises the engine's layout error."""
    monkeypatch.chdir(tmp_path)
    from handyrl_tpu.learner import Trainer

    base = dict(
        TTT_CFG, env={"env": "TicTacToe"}, batch_size=16,
        minimum_episodes=4, maximum_episodes=64, num_batchers=1,
        update_episodes=8, eval_rate=0.1, seed=0, restart_epoch=0,
        updates_per_epoch=4, epochs=1, observation=True,
        device_replay="off", telemetry=False,
        anakin={"mode": "auto", "num_envs": 8},
    )
    env = make_env({"env": "TicTacToe"})
    env.reset()
    model = TPUModel(env.net())
    model.init_params(env.observation(env.players()[0]), seed=0)
    trainer = Trainer(base, model)
    assert trainer.anakin is None       # fell back
    assert trainer.batcher is not None  # IMPALA path intact
    trainer.shutdown()

    base["anakin"] = {"mode": "on", "num_envs": 8}
    with pytest.raises(ValueError, match="observation"):
        Trainer(base, model)


def test_anakin_config_validation_is_jax_free():
    """Config validation must stay importable without jax (the
    pipeline.config convention: CPU processes validate configs before
    pinning a backend) — the anakin package resolves its engine
    lazily so `TrainConfig.__post_init__` never pulls jax in."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "sys.modules['jax'] = None  # any jax import now fails\n"
        "from handyrl_tpu.anakin import AnakinConfig\n"
        "assert AnakinConfig.from_config({'mode': 'on'}).enabled\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    subprocess.run([sys.executable, "-c", code], check=True, cwd=repo)


def test_anakin_trainer_death_shuts_the_learner_down(
        tmp_path, monkeypatch):
    """A dead fused loop can never advance the anakin epoch clock, so
    the server must exit loudly instead of spinning forever serving a
    frozen model (the IMPALA path instead degrades via its intake-
    driven cadence) — and ``run()`` must raise, not return."""
    import threading

    monkeypatch.chdir(tmp_path)
    args = {
        "env_args": {"env": "TicTacToe"},
        "train_args": {
            "turn_based_training": True, "observation": False,
            "gamma": 0.8, "forward_steps": 8, "burn_in_steps": 0,
            "compress_steps": 4, "entropy_regularization": 0.05,
            "entropy_regularization_decay": 0.1,
            "update_episodes": 50, "batch_size": 32,
            "minimum_episodes": 10, "maximum_episodes": 200,
            "epochs": 5, "num_batchers": 1, "eval_rate": 0.1,
            "updates_per_epoch": 5,
            "worker": {"num_parallel": 1}, "lambda": 0.7,
            "policy_target": "TD", "value_target": "TD",
            "seed": 3, "telemetry": False,
            "anakin": {"mode": "on", "num_envs": 16},
        },
        "worker_args": {"num_parallel": 1, "server_address": ""},
    }
    from handyrl_tpu.learner import Learner

    learner = Learner(args)
    real_step = learner.trainer._anakin_step
    calls = {"n": 0}

    def dying_step(*a, **kw):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise RuntimeError("injected device failure")
        return real_step(*a, **kw)

    learner.trainer._anakin_step = dying_step
    raised = []

    def run():
        try:
            learner.run()
        except RuntimeError as exc:
            raised.append(exc)

    runner = threading.Thread(target=run, daemon=True)
    runner.start()
    runner.join(timeout=120)
    assert not runner.is_alive(), (
        "learner.run() hung after the fused loop died")
    assert learner.trainer.failure is not None
    assert learner.shutdown_flag
    # the shutdown is not a clean finish: run() raises after teardown,
    # chained to the step's own exception, so the process exits non-zero
    assert len(raised) == 1
    assert raised[0].__cause__ is learner.trainer.failure
    assert "injected device failure" in str(raised[0].__cause__)


def test_anakin_training_e2e(tmp_path, monkeypatch):
    """Tier-1 acceptance: a real Learner run in anakin mode — fused
    steps drive the epoch clock, workers only evaluate, and every
    epoch record carries the anakin throughput metrics with exactly
    one compile and zero resharding copies."""
    monkeypatch.chdir(tmp_path)
    args = {
        "env_args": {"env": "TicTacToe"},
        "train_args": {
            "turn_based_training": True, "observation": False,
            "gamma": 0.8, "forward_steps": 8, "burn_in_steps": 0,
            "compress_steps": 4, "entropy_regularization": 0.05,
            "entropy_regularization_decay": 0.1,
            "update_episodes": 50, "batch_size": 32,
            "minimum_episodes": 10, "maximum_episodes": 200,
            "epochs": 2, "num_batchers": 1, "eval_rate": 0.1,
            "updates_per_epoch": 6,
            "worker": {"num_parallel": 1}, "lambda": 0.7,
            "policy_target": "TD", "value_target": "TD",
            "seed": 3, "metrics_path": "metrics.jsonl",
            "max_update_compiles": 1, "max_resharding_copies": 1,
            "anakin": {"mode": "on", "num_envs": 32,
                       "opponent_pool": 1},
        },
        "worker_args": {"num_parallel": 1, "server_address": ""},
    }
    from handyrl_tpu.learner import Learner

    learner = Learner(args)
    assert learner.trainer.anakin is not None
    learner.run()

    with open("metrics.jsonl") as f:
        records = [json.loads(line) for line in f if line.strip()]
    assert [r["epoch"] for r in records] == [0, 1]
    for rec in records:
        assert rec["anakin_frames"] >= 5 * 32 * 6   # >= 5 moves/game
        assert rec["anakin_games"] == 32 * 6
        assert rec["anakin_frames_per_sec"] > 0
        assert rec["anakin_games_per_sec"] > 0
        assert rec["retrace_count"] == 1
        assert rec["resharding_copies"] == 0
    assert records[-1]["steps"] == 12
    # the fused step's span family landed in this run's telemetry
    spans = []
    for name in os.listdir("."):
        if name.startswith("spans-") and name.endswith(".jsonl"):
            with open(name) as f:
                spans.extend(json.loads(l) for l in f if l.strip())
    assert any(s.get("name") == "anakin.rollout" for s in spans)
