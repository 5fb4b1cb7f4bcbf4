"""The jitted training step.

The reference's per-batch Python sequence (forward -> backward -> clip
-> Adam step, /root/reference/handyrl/train.py:358-372) becomes ONE
compiled XLA program: ``update_step(params, opt_state, batch) ->
(params, opt_state, metrics)``.  Gradients, clipping, Adam moments and
the parameter update all fuse into a single device launch; under a
device mesh the same program runs SPMD with XLA-inserted gradient
all-reduce (see handyrl_tpu.parallel).

Optimizer parity (/root/reference/handyrl/train.py:328-332,371):
global-norm clip 4.0 -> coupled L2 weight decay 1e-5 (torch-Adam style,
applied before the Adam moments) -> Adam -> lr.  The learning rate is
``3e-8 * data_count_ema / (1 + steps * 1e-5)`` and lives in the
optimizer state as an injected hyperparameter so the host can anneal it
between epochs without recompiling.
"""

from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import optax

from .losses import SEQUENCE, FactoredPolicy, LossConfig, compute_loss

DEFAULT_LR = 3e-8
GRAD_CLIP_NORM = 4.0
WEIGHT_DECAY = 1e-5


def make_optimizer(learning_rate: float) -> optax.GradientTransformation:
    """Torch-Adam-equivalent chain with injected (mutable) lr."""

    def chain(learning_rate):
        return optax.chain(
            optax.clip_by_global_norm(GRAD_CLIP_NORM),
            optax.add_decayed_weights(WEIGHT_DECAY),
            optax.scale_by_adam(),
            optax.scale_by_learning_rate(learning_rate),
        )

    return optax.inject_hyperparams(chain)(learning_rate=learning_rate)


def set_learning_rate(opt_state, lr: float):
    """Anneal the injected lr in-place-ish (returns new state pytree)."""
    opt_state.hyperparams["learning_rate"] = jnp.asarray(lr, jnp.float32)
    return opt_state


def _cast_floats(tree, dtype):
    return jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating) else a,
        tree,
    )


def make_apply_fn(model, compute_dtype="float32") -> Callable:
    """The net's forward for the update step.

    With a low-precision ``compute_dtype`` (bfloat16 on TPU), master
    params stay float32 and only the forward runs low-precision: params,
    observations, and hidden are cast on the way in, head outputs back
    to float32 on the way out — so the matmuls/convs hit the MXU at
    bf16 while the loss math and Adam state keep full precision.
    """
    dtype = jnp.dtype(compute_dtype)
    if dtype == jnp.float32:
        def apply_fn(params, obs, hidden):
            return model.module.apply({"params": params}, obs, hidden)
        return apply_fn

    def apply_fn(params, obs, hidden):
        out = model.module.apply(
            {"params": _cast_floats(params, dtype)},
            _cast_floats(obs, dtype),
            _cast_floats(hidden, dtype),
        )
        # a factored policy stays in the compute dtype: its logits are
        # made float32 a chunk at a time (ops.losses.policy_terms)
        return jax.tree.map(
            lambda a: a if isinstance(a, FactoredPolicy)
            else _cast_floats(a, jnp.float32), out,
            is_leaf=lambda a: isinstance(a, FactoredPolicy))

    return apply_fn


def refresh_target(params, target_params, opt_state, cfg: LossConfig):
    """Next target-network params after one optimizer step (in-jit).

    Polyak (``target_update_tau > 0``) wins over the hard interval
    sync; with neither configured the target freezes (the typed config
    layer rejects that combination for real runs).  The hard sync keys
    off the optimizer's own step count (``InjectHyperparamsState
    .count``), so the cadence survives checkpoints and restarts with
    no extra host traffic."""
    if cfg.target_update_tau > 0.0:
        tau = cfg.target_update_tau
        return jax.tree.map(lambda t, p: t + tau * (p - t),
                            target_params, params)
    if cfg.target_update_interval > 0:
        sync = (opt_state.count % cfg.target_update_interval) == 0
        return jax.tree.map(lambda t, p: jnp.where(sync, p, t),
                            target_params, params)
    return target_params


def make_update_core(model, cfg: LossConfig,
                     optimizer: optax.GradientTransformation,
                     compute_dtype: str = "float32") -> Callable:
    """The un-jitted update-step body — shared by the single-device jit
    below, the sharded wrapper in :mod:`handyrl_tpu.parallel.update`,
    and the fused replay step in :mod:`handyrl_tpu.staging`.

    Signature depends on the configured algorithm (static, so every
    caller builds exactly one shape):

      * standard: ``(params, opt_state, batch) ->
        (params, opt_state, metrics)`` — unchanged;
      * impact:   ``(params, opt_state, batch, target_params) ->
        (params, opt_state, metrics, target_params)`` — the target
        network rides the same jitted program, refreshed per
        :func:`refresh_target`, so the step stays ONE compile.
    """
    apply_fn = make_apply_fn(model, compute_dtype)
    impact = cfg.update_algorithm == "impact"

    def loss_fn(params, batch, hidden, target_params):
        losses, dcnt = compute_loss(apply_fn, params, batch, hidden, cfg,
                                    target_params=target_params)
        return losses["total"], (losses, dcnt)

    def _step(params, opt_state, batch, target_params):
        B = batch["value"].shape[0]
        P = batch["value"].shape[2]
        # a sequence net carries no state across the window's steps
        hidden = SEQUENCE if model.is_sequence \
            else model.init_hidden([B, P])
        grads, (losses, dcnt) = jax.grad(loss_fn, has_aux=True)(
            params, batch, hidden, target_params
        )
        # scopes are HLO metadata only (the step's phases on a device
        # trace, telemetry/devtrace.py); the forward and the loss carry
        # theirs in compute_loss, and the backward pass needs none: its
        # operations read transpose(jvp(<scope>))
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(
                grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            gnorm = optax.global_norm(grads)
            # in-graph nonfinite flag: 1.0 when the loss or the
            # gradient global norm went NaN/Inf this step.  It rides
            # the per-step metrics dict to the ONE per-epoch
            # device_get, where the learner's NumericsGuard counts it
            # — no extra host syncs
            finite = jnp.isfinite(losses["total"]) & jnp.isfinite(gnorm)
        metrics = {**losses, "dcnt": dcnt, "grad_norm": gnorm,
                   "nonfinite": 1.0 - finite.astype(jnp.float32)}
        return params, opt_state, metrics

    if not impact:
        def update_step(params, opt_state, batch):
            return _step(params, opt_state, batch, None)

        return update_step

    def update_step(params, opt_state, batch, target_params):
        params, opt_state, metrics = _step(
            params, opt_state, batch, target_params)
        with jax.named_scope("optimizer"):
            target_params = refresh_target(params, target_params,
                                           opt_state, cfg)
        return params, opt_state, metrics, target_params

    return update_step


def make_update_step(model, cfg: LossConfig,
                     optimizer: optax.GradientTransformation,
                     compute_dtype: str = "float32") -> Callable:
    """Build the jitted ``update_step`` for a TPUModel + config.

    The impact signature additionally donates the target params (the
    step returns their refreshed successor)."""
    core = make_update_core(model, cfg, optimizer, compute_dtype)
    if cfg.update_algorithm == "impact":
        return jax.jit(core, donate_argnums=(0, 1, 3))
    return jax.jit(core, donate_argnums=(0, 1))
