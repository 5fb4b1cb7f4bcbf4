"""Burn-in replay correctness on the recurrent (DRC) path.

Semantics under test (reference train.py:160-174): a training window
starting at ``train_start`` replays ``burn_in_steps`` earlier steps
from a zeroed hidden state to re-warm the RNN — those steps must
produce *identical forward values* to a no-burn-in window covering the
same steps (burn-in changes gradients, never values), and must
contribute *no gradient*: they run as a forward-only scan of their own
(``ops/losses.py::forward_prediction``), so the program holds no
backward pass over them.  The old spelling, ONE scan with a per-step
``where``/``stop_gradient``, lives on here as the plain oracle.
"""

import random
from functools import lru_cache

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from handyrl_tpu.batch import make_batch  # noqa: E402
from handyrl_tpu.environment import make_env  # noqa: E402
from handyrl_tpu.generation import Generator  # noqa: E402
from handyrl_tpu.models import RandomModel, TPUModel  # noqa: E402
from handyrl_tpu.ops import losses  # noqa: E402
from handyrl_tpu.ops.losses import (  # noqa: E402
    LossConfig, compute_loss, forward_prediction)

BURN_IN = 3
TRAIN_STEPS = 5
WINDOW = BURN_IN + TRAIN_STEPS


def geister_cfg(burn_in, forward_steps, observation=False):
    return {
        "turn_based_training": True,
        "observation": observation,
        "gamma": 0.99,
        "forward_steps": forward_steps,
        "burn_in_steps": burn_in,
        "compress_steps": 8,
        "entropy_regularization": 0.1,
        "entropy_regularization_decay": 0.1,
        "lambda": 0.7,
        "policy_target": "TD",
        "value_target": "TD",
    }


@lru_cache(maxsize=None)
def play_geister(observation, steps=16):
    """GeisterNet at seeded weights and one episode of at least
    ``steps`` steps, recorded with or without the idle seat's view
    (played once per view: both fixtures below share it)."""
    random.seed(11)
    env = make_env({"env": "Geister"})
    env.reset()
    model = TPUModel(env.net())
    obs0 = env.observation(env.players()[0])
    model.init_params(obs0, seed=11)
    rollout = RandomModel(model, obs0)
    players = env.players()
    job = {"player": players, "model_id": {p: 1 for p in players}}
    gen = Generator(env, geister_cfg(0, WINDOW, observation))
    episode = None
    while episode is None or episode["steps"] < steps:
        episode = gen.generate({p: rollout for p in players}, job)
    return model, episode


@pytest.fixture(scope="module")
def geister_setup():
    return play_geister(False)


@pytest.fixture(scope="module", params=[False, True],
                ids=["turn_seat", "observation"])
def geister_by_view(request):
    return (request.param,) + play_geister(request.param)


def window_batch(episode, cfg, start, train_start, end):
    cmp = cfg["compress_steps"]
    st_block, ed_block = start // cmp, (end - 1) // cmp + 1
    sel = {
        "args": episode["args"], "outcome": episode["outcome"],
        "moment": episode["moment"][st_block:ed_block],
        "base": st_block * cmp,
        "start": start, "end": end, "train_start": train_start,
        "total": episode["steps"],
    }
    return jax.tree.map(jnp.asarray, make_batch([sel], cfg))


def net_apply(model):
    return lambda params, obs, hidden: model.module.apply(
        {"params": params}, obs, hidden)


def zero_hidden(model, batch):
    B, P = batch["value"].shape[0], batch["value"].shape[2]
    return model.init_hidden([B, P])


def run_forward(model, batch, cfg_dict):
    return forward_prediction(
        net_apply(model), model.params, zero_hidden(model, batch), batch,
        LossConfig.from_config(cfg_dict))


@pytest.mark.parametrize("start,train_start", [
    (2, 2 + BURN_IN),  # replay begins mid-episode: hidden re-warmed from zero
    (0, 1),  # the episode starts INSIDE the burn-in stretch: two padded
             # steps (observation mask zero) and one real one hand the
             # hidden state over to the trained scan
], ids=["mid_episode", "episode_starts_in_burn_in"])
def test_burn_in_forward_values_match_plain_window(
        geister_setup, start, train_start):
    """The training steps of a burn-in window produce the same forward
    values as the same steps in a burn-in-free window starting at the
    same replay point; the burn-in steps themselves yield no output."""
    model, episode = geister_setup
    warm = train_start - start  # real steps replayed ahead of training
    end = train_start + TRAIN_STEPS

    cfg_burn = geister_cfg(BURN_IN, TRAIN_STEPS)
    batch_burn = window_batch(episode, cfg_burn, start, train_start, end)
    assert float(batch_burn["observation_mask"][:, :BURN_IN - warm].sum()) == 0
    assert float(batch_burn["observation_mask"][:, BURN_IN - warm:].sum()) > 0

    cfg_plain = geister_cfg(0, warm + TRAIN_STEPS)
    batch_plain = window_batch(episode, cfg_plain, start, start, end)

    out_burn = run_forward(model, batch_burn, cfg_burn)
    out_plain = run_forward(model, batch_plain, cfg_plain)

    for key in ("policy", "value"):
        assert out_burn[key].shape[1] == TRAIN_STEPS
        np.testing.assert_allclose(
            np.asarray(out_burn[key]),
            np.asarray(out_plain[key][:, warm:]),
            rtol=1e-5, atol=1e-5, err_msg=key)


def test_burn_in_blocks_gradient_to_initial_hidden(geister_setup):
    """With burn_in > 0 the forward-only burn-in scan severs the path
    from the training loss back to the initial hidden state; with
    burn_in=0 that path carries gradient."""
    model, episode = geister_setup
    start = 2

    def hidden_grad_norm(burn_in):
        forward = TRAIN_STEPS if burn_in else WINDOW
        cfg_d = geister_cfg(burn_in, forward)
        batch = window_batch(
            episode, cfg_d, start, start + burn_in, start + WINDOW)
        cfg = LossConfig.from_config(cfg_d)

        def loss_of_hidden(hidden):
            out = forward_prediction(
                net_apply(model), model.params, hidden, batch, cfg)
            return sum(jnp.sum(v ** 2) for v in out.values())

        hidden0 = jax.tree.map(
            lambda h: h + 0.1,  # non-zero so a live path shows up
            zero_hidden(model, batch))
        grads = jax.grad(loss_of_hidden)(hidden0)
        return float(sum(jnp.sum(jnp.abs(g))
                         for g in jax.tree.leaves(grads)))

    assert hidden_grad_norm(BURN_IN) == pytest.approx(0.0, abs=1e-8)
    assert hidden_grad_norm(0) > 1e-4

# ---------------------------------------------------------------------------
# the program's shape, and its gradient against the old spelling
# ---------------------------------------------------------------------------

WINDOWS = [(0, 8), (3, 5), (4, 8)]  # (burn_in_steps, forward_steps)


def one_scan_forward_prediction(apply_fn, params, hidden, batch, cfg):
    """The plain oracle: the recurrent path as it was spelled before the
    split. ONE scan over the whole window, every burn-in step's outputs
    and hidden leaves under ``where(burn, stop_gradient(v), v)``, the
    burn-in outputs sliced off at the end."""
    B, T, P_in = batch["action"].shape[:3]
    b = cfg.burn_in_steps
    omask_full = batch["observation_mask"]
    single_seat = cfg.turn_based_training and not cfg.observation
    P_model = 1 if single_seat else omask_full.shape[2]

    def step(hidden, xs):
        obs_t, omask_t, t = xs

        def mask_like(h):
            return omask_t.reshape(omask_t.shape[:2] + (1,) * (h.ndim - 2))

        h_in = jax.tree.map(lambda h: h * mask_like(h), hidden)
        if single_seat:
            h_in = jax.tree.map(lambda h: h.sum(axis=1), h_in)
        else:
            h_in = jax.tree.map(
                lambda h: h.reshape((-1,) + h.shape[2:]), h_in)
        obs_flat = jax.tree.map(
            lambda a: a.reshape((-1,) + a.shape[2:]), obs_t)
        out = {k: v for k, v in apply_fn(params, obs_flat, h_in).items()
               if v is not None}
        next_hidden = jax.tree.map(
            lambda h: h.reshape((B, P_model) + h.shape[1:]),
            out.pop("hidden"))
        out = {k: v.reshape((B, P_in) + v.shape[1:]) for k, v in out.items()}
        out, next_hidden = jax.tree.map(
            lambda v: jnp.where(t < b, jax.lax.stop_gradient(v), v),
            (out, next_hidden))
        new_hidden = jax.tree.map(
            lambda h, nh: h * (1 - mask_like(h)) + nh * mask_like(h),
            hidden, next_hidden)
        return new_hidden, out

    xs = (jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), batch["observation"]),
          jnp.moveaxis(omask_full, 1, 0), jnp.arange(T))
    _, outs = jax.lax.scan(step, hidden, xs)
    outputs = {k: jnp.moveaxis(v, 0, 1)[:, b:] for k, v in outs.items()}
    policy = outputs["policy"] * batch["turn_mask"][:, b:]
    if policy.shape[2] > P_in:
        policy = policy.sum(axis=2, keepdims=True)
    result = {k: v * batch["observation_mask"][:, b:]
              for k, v in outputs.items() if k != "policy"}
    result["policy"] = policy - batch["action_mask"][:, b:]
    return result


def loss_of_params(geister_by_view, burn_in, forward_steps):
    """``params -> total loss`` of ``compute_loss`` on one window of the
    episode, and the parameters to take it at."""
    observation, model, episode = geister_by_view
    cfg_d = geister_cfg(burn_in, forward_steps, observation)
    start = 1
    batch = window_batch(episode, cfg_d, start, start + burn_in,
                         start + burn_in + forward_steps)
    hidden = zero_hidden(model, batch)
    cfg = LossConfig.from_config(cfg_d)

    def loss(params):
        return compute_loss(
            net_apply(model), params, batch, hidden, cfg)[0]["total"]

    return loss, model.params


def net_scans(jaxpr):
    """(length, reverse) of every ``scan`` whose body runs a convolution
    (the net; the targets' reverse scans run none), outermost first."""
    def subjaxprs(eqn):
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield j

    def convolves(j):
        return any(e.primitive.name == "conv_general_dilated"
                   or any(convolves(s) for s in subjaxprs(e))
                   for e in j.eqns)

    found = []
    for eqn in jaxpr.eqns:
        inner = list(subjaxprs(eqn))
        if eqn.primitive.name == "scan" and convolves(inner[0]):
            found.append((eqn.params["length"], eqn.params["reverse"]))
        else:
            for j in inner:
                found += net_scans(j)
    return found


@pytest.mark.parametrize("burn_in,forward_steps", WINDOWS)
def test_no_backward_pass_runs_over_the_burn_in_steps(
        geister_by_view, burn_in, forward_steps):
    """In the gradient's program the net is scanned forward over the
    burn-in steps, forward over the trained steps and backward over the
    trained steps: no scan has the whole window's length."""
    loss, params = loss_of_params(geister_by_view, burn_in, forward_steps)
    scans = net_scans(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    trained = [(forward_steps, False), (forward_steps, True)]
    assert scans == ([(burn_in, False)] if burn_in else []) + trained


@pytest.mark.parametrize("burn_in,forward_steps", WINDOWS)
def test_gradient_equals_the_one_scan_spelling(
        geister_by_view, burn_in, forward_steps, monkeypatch):
    """Leaving out the terms that were exactly zero changes no
    parameter's gradient."""
    loss, params = loss_of_params(geister_by_view, burn_in, forward_steps)
    value, grads = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(
        losses, "forward_prediction", one_scan_forward_prediction)
    value_old, grads_old = jax.jit(jax.value_and_grad(loss))(params)
    scans_old = net_scans(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert [n for n, _ in scans_old] == [burn_in + forward_steps] * 2

    np.testing.assert_allclose(float(value), float(value_old), rtol=1e-6)
    flat, flat_old = (jax.tree_util.tree_leaves_with_path(g)
                      for g in (grads, grads_old))
    assert [p for p, _ in flat] == [p for p, _ in flat_old]
    for (path, g), (_, g_old) in zip(flat, flat_old):
        assert g.dtype == g_old.dtype == jnp.float32
        # a leaf no head of this window reaches reads nought on both sides
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(g_old), rtol=0,
            atol=1e-6 * float(jnp.abs(g_old).max()),
            err_msg=jax.tree_util.keystr(path))
    assert max(float(jnp.abs(g).max()) for _, g in flat) > 1e-3
