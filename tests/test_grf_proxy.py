"""GRF-scale workload (capability config #5): (72, 96, 16) SMM-sized
observations, long episodes, recurrent net with burn-in replay.

The drill env generates GRF-shaped traffic (handyrl_tpu/envs/grf_proxy
docstring); these tests pin the full training path at that geometry —
generation -> wire episodes -> device replay ring (uint8 storage) ->
burn-in batch -> DRC update step."""

import random

import numpy as np
import pytest

CFG = {
    "turn_based_training": False,   # simultaneous: seat-mode training
    "observation": False,
    "gamma": 0.993,                 # long-horizon discount
    "forward_steps": 8,
    "burn_in_steps": 4,
    "compress_steps": 8,
    "entropy_regularization": 0.1,
    "entropy_regularization_decay": 0.1,
    "lambda": 0.7,
    "policy_target": "UPGO",
    "value_target": "TD",
    "transfer_dtype": "uint8",
    "compute_dtype": "bfloat16",
}


def _episodes(count, max_steps=96, seed=5):
    from handyrl_tpu.environment import make_env
    from handyrl_tpu.generation import Generator
    from handyrl_tpu.models import RandomModel, TPUModel

    random.seed(seed)
    env = make_env({"env": "GRFProxy", "max_steps": max_steps})
    env.reset()
    model = TPUModel(env.net())
    obs0 = env.observation(0)
    assert obs0.shape == (72, 96, 16)
    assert np.array_equal(obs0, obs0.astype(np.uint8))  # binary planes
    model.init_params(obs0, seed=seed)
    rollout = RandomModel(model, obs0)
    gen = Generator(env, CFG)
    players = env.players()
    job = {"player": players, "model_id": {p: 1 for p in players}}
    eps = []
    while len(eps) < count:
        ep = gen.generate({p: rollout for p in players}, job)
        if ep is not None:
            eps.append(ep)
    return env, model, eps


def test_net_carries_state_and_update_steps(tmp_path):
    """One fused device-replay update at the GRF geometry: ring stores
    uint8, gather dequantizes, the DRC hidden threads burn-in."""
    import jax
    import jax.numpy as jnp

    from handyrl_tpu.ops.losses import LossConfig
    from handyrl_tpu.ops.update import make_optimizer
    from handyrl_tpu.staging import (
        DeviceReplay, make_replay_update_step, step_state)

    env, model, eps = _episodes(3)
    replay = DeviceReplay(CFG, capacity=8, max_bytes=2 << 30)
    replay.offer(eps)
    replay.ingest()
    assert replay.size == 3
    assert replay.t_max >= max(e["steps"] for e in eps)

    optimizer = make_optimizer(1e-3)
    params = jax.tree.map(jnp.asarray, model.params)
    opt_state = optimizer.init(params)
    update = make_replay_update_step(
        replay, model, LossConfig.from_config(CFG), optimizer,
        "bfloat16", batch_size=4, seed=0)
    state = step_state(replay, 0)
    params, opt_state, metrics, (ring, sums) = update(
        params, opt_state, replay.buffers, state)
    assert np.isfinite(float(metrics["total"]))
    assert int(ring[2]) == 1  # device-side step counter advanced
    # ... and the step's metrics went into the epoch's sums
    assert int(sums["steps"]) == 1
    assert float(sums["total"]) == float(metrics["total"])
    assert int(sums["dcnt"]) == float(metrics["dcnt"]) > 0


def test_ring_budget_caps_at_grf_byte_cost():
    """At ~MB-scale episodes the byte budget must bite: a small
    device_replay_mb cap shrinks the ring instead of OOMing."""
    from handyrl_tpu.staging import DeviceReplay

    _, _, eps = _episodes(2, max_steps=64)
    replay = DeviceReplay(CFG, capacity=4096, max_bytes=64 << 20)
    replay.offer(eps)
    replay.ingest()
    # (72*96*16 uint8 + narrow lane-padded channels) * t_max ~= 14 MB
    # per slot -> 64 MiB holds only a handful of slots
    assert replay.capacity <= 8
    assert replay.size == 2
    batch = replay.sample(2)
    obs = batch["observation"]
    leaf = obs if not isinstance(obs, dict) else list(obs.values())[0]
    assert leaf.shape[-3:] == (72, 96, 16)


def test_scripted_chaser_beats_random():
    from handyrl_tpu.environment import make_env

    random.seed(3)
    env = make_env({"env": "GRFProxy", "max_steps": 400})
    wins = 0
    for _ in range(5):
        env.reset()
        while not env.terminal():
            env.step({0: env.rule_based_action(0),
                      1: random.choice(env.legal_actions(1))})
        wins += env.outcome()[0] > 0
    assert wins >= 4  # the chaser overwhelms a random walker