"""Forward prediction and loss composition — the jitted learner math.

Semantic parity with /root/reference/handyrl/train.py:128-268:
  * feed-forward nets run one big flattened forward over (B*T*P, ...)
    — MXU-friendly: a single large batched matmul/conv stream;
  * recurrent nets run a ``lax.scan`` over time with observation-mask
    hidden blending, turn-based hidden gathering, and gradient-free
    burn-in (a forward-only scan of its own ahead of the trained one —
    GroupNorm models have no train/eval mode divergence, so burn-in
    needs no mode switch);
  * sequence nets (``SEQUENCE``) take the window AS the sequence: one
    causal pass over ``(B, T)`` tokens, no carried state, the policy
    factored (``FactoredPolicy``) so that logits of vocabulary width
    exist a chunk of positions at a time; a net with a next-next-token
    module hands back a second factored prediction, whose
    cross-entropy against the window's own token two rows on is added
    to the total (``nextn_term``);
  * losses: V-Trace/UPGO/TD/MC targets on detached values, importance
    ratios clipped at ``rho_clip``/``c_clip`` (both 1 by default, the
    reference behavior), two-player zero-sum value symmetrization,
    terminal outcome bootstrap, entropy regularization decayed by
    episode progress;
  * ``update_algorithm: impact`` (IMPACT, arXiv:1912.00167) swaps the
    policies behind the math: importance ratios are computed against a
    maintained TARGET network instead of the live learner policy (so
    V-Trace corrections stay stable however stale the episodes are),
    and the policy loss becomes a PPO-style two-sided surrogate clip of
    the current/target ratio.  The target params ride the jitted update
    step (ops.update) and refresh by hard sync or Polyak average.

Everything here is pure and traced once per batch geometry.
"""

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .targets import compute_target

# reference defaults for the importance-ratio clips; the live values
# come from LossConfig (rho_clip / c_clip surface them as config keys)
CLIP_RHO = 1.0
CLIP_C = 1.0

# in ``hidden``'s place for a net whose window is the sequence: it
# carries no state from step to step (``TPUModel.is_sequence``)
SEQUENCE = "sequence"
POLICY_CHUNK = 1024     # positions whose logits exist together
# weight of a net's next-next-token term beside the RL loss (the
# family's own, late in its training; the net's config gives none)
NEXTN_WEIGHT = 0.1


@jax.tree_util.register_pytree_node_class
class FactoredPolicy:
    """Policy logits not yet multiplied out: ``features (..., d)`` of
    the trunk and the head's ``kernel (d, actions)``.  At a vocabulary
    of actions the logits of a whole window are gigabytes, so the loss
    takes what it needs of them (``policy_terms``) chunk by chunk."""

    def __init__(self, features, kernel):
        self.features, self.kernel = features, kernel

    def tree_flatten(self):
        return (self.features, self.kernel), None

    @classmethod
    def tree_unflatten(cls, _aux, children):
        return cls(*children)


def rows_on(tokens, n):
    """A window's tokens ``(B, T)`` moved ``n`` rows on: at row ``t``
    the token of row ``t + n``, -1 where the window has none."""
    return jnp.concatenate(
        [tokens[:, n:], jnp.full_like(tokens[:, :n], -1)], 1)


class LossConfig(NamedTuple):
    """Static (trace-time) training hyper-parameters."""

    turn_based_training: bool
    observation: bool
    burn_in_steps: int
    lambda_: float
    gamma: float
    policy_target: str
    value_target: str
    entropy_regularization: float
    entropy_regularization_decay: float
    # off-policy correction knobs (defaults keep existing runs
    # bit-identical; read with .get so raw pre-PR config dicts work)
    rho_clip: float = CLIP_RHO
    c_clip: float = CLIP_C
    # "standard" = live-policy ratios + score-function policy loss;
    # "impact" = target-network ratios + clipped surrogate objective
    update_algorithm: str = "standard"
    surrogate_clip: float = 0.2
    # target-network refresh cadence (impact only): hard sync every
    # `target_update_interval` optimizer steps, or Polyak averaging
    # with `target_update_tau` when > 0 (tau wins if both are set)
    target_update_interval: int = 0
    target_update_tau: float = 0.0

    @classmethod
    def from_config(cls, cfg) -> "LossConfig":
        return cls(
            turn_based_training=bool(cfg["turn_based_training"]),
            observation=bool(cfg["observation"]),
            burn_in_steps=int(cfg["burn_in_steps"]),
            lambda_=float(cfg["lambda"]),
            gamma=float(cfg["gamma"]),
            policy_target=str(cfg["policy_target"]),
            value_target=str(cfg["value_target"]),
            entropy_regularization=float(cfg["entropy_regularization"]),
            entropy_regularization_decay=float(cfg["entropy_regularization_decay"]),
            rho_clip=float(cfg.get("rho_clip", CLIP_RHO) or CLIP_RHO),
            c_clip=float(cfg.get("c_clip", CLIP_C) or CLIP_C),
            update_algorithm=str(
                cfg.get("update_algorithm", "standard") or "standard"),
            surrogate_clip=float(cfg.get("surrogate_clip", 0.2) or 0.2),
            target_update_interval=int(
                cfg.get("target_update_interval", 0) or 0),
            target_update_tau=float(
                cfg.get("target_update_tau", 0.0) or 0.0),
        )


def _flatten_lead(tree, n):
    return jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[n:]), tree
    )


def forward_prediction(apply_fn: Callable, params, hidden, batch,
                       cfg: LossConfig) -> Dict[str, jnp.ndarray]:
    """Run the net over a (B, T, P_in, ...) batch -> (B, T - b, P_in/P,
    ...): the trained steps only, ``b = cfg.burn_in_steps``.

    ``hidden`` is the initial (B, P, ...) recurrent state or None; a
    recurrent net spends the first ``b`` steps warming it, forward only.
    """
    observations = batch["observation"]
    B, T, P_in = batch["action"].shape[:3]
    b = cfg.burn_in_steps

    if hidden is SEQUENCE:
        # the window is the sequence: one seat's tokens (B, T) in one
        # causal pass; a seat axis of one goes back on what comes out
        # positions past the episode's end go in as -1: they reach no
        # loss term and no real position, and take no expert
        tokens = jnp.where(batch["episode_mask"][:, :, 0, 0] > 0,
                           observations[:, :, 0], -1)
        out = apply_fn(params, tokens, None)
        policy = out["policy"]
        # no bias in the head: masking the features masks the logits;
        # an all-legal batch has no action mask to take off (width 0)
        result = {
            "policy": FactoredPolicy(
                policy.features[:, :, None] * batch["turn_mask"].astype(
                    policy.features.dtype), policy.kernel),
            "value": out["value"][:, :, None] * batch["observation_mask"],
            # what the net counted beside its heads, whatever it has
            "counts": out["counts"],
        }
        if "mtp" in out:
            # a net with a next-next-token module: its second
            # prediction at ``t`` is of the window's own token two rows
            # on (-1 where the episode has none: no term there)
            result["mtp"] = out["mtp"]
            result["mtp_target"] = rows_on(tokens, 2)
        return result
    if hidden is None:
        obs_flat = _flatten_lead(observations, 3)  # (B*T*P_in, ...)
        out = apply_fn(params, obs_flat, None)
        outputs = {
            k: v.reshape((B, T, P_in) + v.shape[1:])[:, b:]
            for k, v in out.items()
            if v is not None
        }
    else:
        omask_full = batch["observation_mask"]  # (B, T, P, 1)
        # seats the net was applied to this step: the single acting seat
        # in turn-based mode, every player otherwise
        P_model = 1 if (cfg.turn_based_training and not cfg.observation) \
            else omask_full.shape[2]

        def step(params, hidden, xs):
            obs_t, omask_t = xs  # (B, P_in, ...), (B, P, 1)

            # zero hidden where the player did not observe (episode
            # starts inside the window restart the recurrence)
            def mask_like(h):
                return omask_t.reshape(omask_t.shape[:2] + (1,) * (h.ndim - 2))

            h_masked = jax.tree.map(lambda h: h * mask_like(h), hidden)
            if cfg.turn_based_training and not cfg.observation:
                # only the turn player's hidden is non-zero: the P-sum
                # gathers it into the single acting seat
                h_in = jax.tree.map(lambda h: h.sum(axis=1), h_masked)
            else:
                h_in = _flatten_lead(h_masked, 2)  # (B*P, ...)

            obs_flat = _flatten_lead(obs_t, 2)  # (B*P_in, ...)
            out = apply_fn(params, obs_flat, h_in)
            out = {
                k: v.reshape((B, P_in) + v.shape[1:]) if k != "hidden"
                else v
                for k, v in out.items()
                if v is not None
            }
            next_hidden = out.pop("hidden")
            next_hidden = jax.tree.map(
                lambda h: h.reshape((B, P_model) + h.shape[1:]),
                next_hidden,
            )

            # write the new hidden into observed seats only
            new_hidden = jax.tree.map(
                lambda h, nh: h * (1 - mask_like(h)) + nh * mask_like(h),
                hidden,
                next_hidden,
            )
            return new_hidden, out

        xs = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0),
                          (observations, omask_full))
        if b > 0:
            # burn-in contributes no gradient: nothing differentiable
            # goes in, so no backward pass is built and no residual
            # kept; nothing per step comes out, so the heads drop too
            hidden, _ = lax.scan(
                lambda h, x: (step(lax.stop_gradient(params), h, x)[0], None),
                lax.stop_gradient(hidden),
                jax.tree.map(lambda a: a[:b], xs))
        _, outs = lax.scan(partial(step, params), hidden,
                           jax.tree.map(lambda a: a[b:], xs))
        outputs = {k: jnp.moveaxis(v, 0, 1) for k, v in outs.items()}

    # mask heads: policy by turn, scalar heads by observation
    result = {}
    for k, o in outputs.items():
        if k == "policy":
            o = o * batch["turn_mask"][:, b:]  # may broadcast P_in -> P
            if o.shape[2] > P_in:
                # turn-alternating batch: collapse back to the acting seat
                o = o.sum(axis=2, keepdims=True)
            result[k] = o - batch["action_mask"][:, b:]
        else:
            result[k] = o * batch["observation_mask"][:, b:]
    return result


def _huber(x):
    """Smooth-L1 with delta=1 (matches F.smooth_l1_loss)."""
    absx = jnp.abs(x)
    return jnp.where(absx < 1.0, 0.5 * x * x, absx - 0.5)


def _masked_entropy(logits, axis=-1):
    """Categorical entropy that is exact-zero-safe for -1e32 masked
    logits (softmax underflows to exactly 0, and 0 * finite = 0)."""
    lsm = jax.nn.log_softmax(logits, axis=axis)
    p = jnp.exp(lsm)
    return -jnp.sum(p * jnp.clip(lsm, -1e32, 0.0), axis=axis)


def _head_in_chunks(policy, taken, scope):
    """``(log-probability of the entries taken, entropy)`` of a
    ``FactoredPolicy`` over flat positions: logits made
    ``POLICY_CHUNK`` positions at a time and made again coming back,
    never whole, under the net scope ``scope``."""
    feats = policy.features.reshape(-1, policy.features.shape[-1])
    n = taken.size
    pad = -n % POLICY_CHUNK
    feats = jnp.pad(feats, [(0, pad), (0, 0)])
    taken = jnp.pad(taken.reshape(-1), (0, pad))

    @jax.checkpoint
    def chunk(kernel, xs):
        f, a = xs
        logits = jnp.dot(f, kernel, preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        p = jax.nn.softmax(logits, axis=-1)
        selected = jnp.take_along_axis(logits, a[:, None], axis=-1)[:, 0]
        return selected - lse, lse - (p * logits).sum(-1)

    with jax.named_scope("net.forward"), jax.named_scope(scope):
        selected, entropy = lax.map(
            partial(chunk, policy.kernel),
            (feats.reshape(-1, POLICY_CHUNK, feats.shape[-1]),
             taken.reshape(-1, POLICY_CHUNK)))
    return selected.reshape(-1)[:n], entropy.reshape(-1)[:n]


def policy_terms(policy, actions):
    """``(log-probability of the actions taken (..., 1), entropy (...)
    or None)`` of ``policy``.  Dense logits: the log-softmax's entry,
    and the entropy is left to ``compose_losses`` as it always was.  A
    ``FactoredPolicy``: both, from logits made ``POLICY_CHUNK``
    positions at a time and made again coming back, never whole."""
    if not isinstance(policy, FactoredPolicy):
        log_policy = jax.nn.log_softmax(policy, axis=-1)
        return jnp.take_along_axis(log_policy, actions, axis=-1), None
    selected, entropy = _head_in_chunks(policy, actions, "net.head")
    return (selected.reshape(actions.shape),
            entropy.reshape(actions.shape[:-1]))


def nextn_term(policy, target):
    """A net's next-next-token term: ``policy`` (a ``FactoredPolicy``
    over ``(B, T)`` positions, the module's features through the
    model's own head) against ``target (B, T)``, the token two rows on
    or -1 where the window holds none.  Each row's mean cross-entropy
    over the positions that have a target, summed over the rows as
    every term of the loss is; and the share of positions that have
    one."""
    there = target >= 0
    log_p, _ = _head_in_chunks(policy, jnp.maximum(target, 0), "net.mtp")
    with jax.named_scope("net.forward"), jax.named_scope("net.mtp"):
        log_p = jnp.where(there, log_p.reshape(target.shape), 0.0)
        term = (-log_p.sum(-1) / jnp.maximum(there.sum(-1), 1)).sum()
    return term, there.mean()


def compose_losses(outputs, log_selected_policies, total_advantages,
                   targets, batch, cfg: LossConfig, policy_loss=None,
                   entropy=None):
    """Combine policy / value / return / entropy losses (summed, not
    averaged — the lr schedule normalizes by the data-count EMA).

    ``policy_loss`` (per-element, pre-mask) replaces the default
    score-function term when given — the IMPACT surrogate plugs in
    here without duplicating the rest of the composition.  ``entropy``
    (per acting seat) comes with a factored policy, whose logits are
    not there to take it from."""
    tmasks = batch["turn_mask"]
    omasks = batch["observation_mask"]

    losses = {}
    dcnt = tmasks.sum()

    if policy_loss is None:
        policy_loss = -log_selected_policies * total_advantages
    losses["p"] = (policy_loss * tmasks).sum()
    if "value" in outputs:
        losses["v"] = (
            ((outputs["value"] - targets["value"]) ** 2) * omasks
        ).sum() / 2
    if "return" in outputs:
        losses["r"] = (
            _huber(outputs["return"] - targets["return"]) * omasks
        ).sum()

    if entropy is None:
        entropy = _masked_entropy(outputs["policy"])
    entropy = entropy * tmasks.sum(-1)  # (B,T,P)
    losses["ent"] = entropy.sum()

    base_loss = losses["p"] + losses.get("v", 0.0) + losses.get("r", 0.0)
    decay_weight = 1.0 - batch["progress"] * (
        1.0 - cfg.entropy_regularization_decay
    )
    entropy_loss = (entropy * decay_weight).sum() * -cfg.entropy_regularization
    losses["total"] = base_loss + entropy_loss

    return losses, dcnt


def compute_loss(apply_fn: Callable, params, batch, hidden, cfg: LossConfig,
                 target_params=None):
    """Full forward + target computation + loss composition.

    With ``cfg.update_algorithm == "impact"`` and ``target_params``
    given, a second (gradient-free) forward through the target network
    provides the correction policy and the bootstrap values: V-Trace
    ratios are target/behavior, the policy loss is the clipped
    surrogate of current/target, and the reported ``clip_frac`` is the
    fraction of acting steps whose surrogate ratio hit the clip."""
    impact = cfg.update_algorithm == "impact" and target_params is not None
    # the named scopes are HLO metadata only: the update step's phases
    # on a device trace (telemetry/devtrace.py).  ``loss.targets`` is
    # the math on detached values, ``loss.terms`` what the gradient
    # flows through outside the net.
    with jax.named_scope("net.forward"):
        outputs = forward_prediction(apply_fn, params, hidden, batch, cfg)
    tgt_outputs = None
    if impact:
        # gradients only flow w.r.t. `params` (grad argnums in the
        # update core), but stop_gradient keeps the trace honest even
        # if a caller differentiates more broadly
        with jax.named_scope("net.forward"):
            tgt_outputs = forward_prediction(
                apply_fn, target_params, hidden, batch, cfg)
        tgt_outputs = {k: lax.stop_gradient(v)
                       for k, v in tgt_outputs.items()}
    if cfg.burn_in_steps > 0:
        b = cfg.burn_in_steps
        batch = {
            k: v[:, b:] if v.shape[1] > 1 else v for k, v in batch.items()
            if k != "observation"
        } | {"observation": batch["observation"]}

    actions = batch["action"]
    emasks = batch["episode_mask"]
    omasks = batch["observation_mask"]
    tmasks = batch["turn_mask"]
    value_target_masks, return_target_masks = omasks, omasks

    with jax.named_scope("loss.targets"):
        log_selected_b = (
            jnp.log(jnp.clip(batch["selected_prob"], 1e-16, 1.0)) * emasks
        )
    with jax.named_scope("loss.terms"):
        log_selected_t, entropy = policy_terms(outputs["policy"], actions)
        log_selected_t = log_selected_t * emasks
    log_selected_g = None
    with jax.named_scope("loss.targets"):
        if impact:
            log_selected_g = policy_terms(
                tgt_outputs["policy"], actions)[0] * emasks

    with jax.named_scope("loss.targets"):
        # importance-sampling ratios (behavior -> correction policy),
        # clipped at rho_clip/c_clip.  Standard: the live learner policy.
        # IMPACT: the target network's policy — stable under staleness,
        # because the correction target moves on the sync cadence instead
        # of every optimizer step.
        if impact:
            log_rhos = log_selected_g - log_selected_b
        else:
            log_rhos = lax.stop_gradient(log_selected_t) - log_selected_b
        # exp of an unbounded log-ratio overflows to inf on the first
        # badly-stale batch; +/-20 is far beyond the useful range (the
        # ratios are clipped to rho_clip/c_clip right below) but keeps
        # the op finite
        rhos = jnp.exp(jnp.clip(log_rhos, -20.0, 20.0))
        clipped_rhos = jnp.clip(rhos, 0.0, cfg.rho_clip)
        cs = jnp.clip(rhos, 0.0, cfg.c_clip)

        if impact:
            # IMPACT bootstraps targets from the TARGET network's heads
            outputs_nograd = dict(tgt_outputs)
        else:
            outputs_nograd = {k: lax.stop_gradient(v)
                              for k, v in outputs.items()}

        if "value" in outputs_nograd:
            values_nograd = outputs_nograd["value"]
            if cfg.turn_based_training and values_nograd.shape[2] == 2:
                # two-player zero-sum: average own value with the negated
                # opponent view wherever either observed
                values_opp = -jnp.flip(values_nograd, axis=2)
                omasks_opp = jnp.flip(omasks, axis=2)
                values_nograd = (
                    values_nograd * omasks + values_opp * omasks_opp
                ) / (omasks + omasks_opp + 1e-8)
                value_target_masks = jnp.clip(omasks + omasks_opp, 0.0, 1.0)
            # beyond the terminal step the target is the final outcome
            outputs_nograd["value"] = (
                values_nograd * emasks + batch["outcome"] * (1 - emasks)
            )

        targets, advantages = {}, {}
        value_args = (
            outputs_nograd.get("value", None), batch["outcome"], None,
            cfg.lambda_, 1.0, clipped_rhos, cs, value_target_masks,
        )
        return_args = (
            outputs_nograd.get("return", None), batch["return"], batch["reward"],
            cfg.lambda_, cfg.gamma, clipped_rhos, cs, return_target_masks,
        )

        targets["value"], advantages["value"] = compute_target(
            cfg.value_target, *value_args
        )
        targets["return"], advantages["return"] = compute_target(
            cfg.value_target, *return_args
        )
        if cfg.policy_target != cfg.value_target:
            _, advantages["value"] = compute_target(cfg.policy_target, *value_args)
            _, advantages["return"] = compute_target(cfg.policy_target, *return_args)

    with jax.named_scope("loss.terms"):
        denom = tmasks.sum() + 1e-8
        if impact:
            # IMPACT surrogate objective: the V-Trace rho factor is
            # replaced by the current/target ratio under a two-sided PPO
            # clip — maximize min(r*A, clip(r, 1-eps, 1+eps)*A)
            adv = sum(advantages.values())
            # same finite-exp discipline as the rhos above: the surrogate
            # clip bounds the USED ratio to 1 +/- eps, so clamping the
            # exponent changes nothing numerically useful
            ratio = jnp.exp(jnp.clip(log_selected_t - log_selected_g,
                                     -20.0, 20.0))
            eps = cfg.surrogate_clip
            surrogate = jnp.minimum(
                ratio * adv, jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * adv)
            policy_loss = -surrogate
            clip_frac = (
                (jnp.abs(ratio - 1.0) > eps) * tmasks).sum() / denom
            losses, dcnt = compose_losses(
                outputs, log_selected_t, None, targets, batch, cfg,
                policy_loss=policy_loss, entropy=entropy)
        else:
            total_advantages = clipped_rhos * sum(advantages.values())
            # how often the rho clip actually engaged: the off-policy
            # pressure signal (0 on fresh data; grows with staleness)
            clip_frac = ((rhos > cfg.rho_clip) * tmasks).sum() / denom
            losses, dcnt = compose_losses(
                outputs, log_selected_t, total_advantages, targets, batch,
                cfg, entropy=entropy)
    losses["clip_frac"] = clip_frac
    if hidden is SEQUENCE:
        losses.update(sequence_counters(outputs["counts"], emasks))
    if "mtp" in outputs:
        with jax.named_scope("loss.terms"):
            losses["mtp_loss"], losses["mtp_target_share"] = nextn_term(
                outputs["mtp"], outputs["mtp_target"])
            losses["total"] = (
                losses["total"] + NEXTN_WEIGHT * losses["mtp_loss"])
    return losses, dcnt


# what a sequence net's step counts beside its losses: they ride the
# step's ``metrics`` and ``Trainer.step_profile`` reads them
SEQUENCE_COUNTERS = ("expert_load_max", "expert_load_mean",
                     "held_pick_share", "window_fill",
                     "mtp_loss", "mtp_target_share", "delta_retention")


def sequence_counters(counts, episode_mask):
    """What a sequence net counted over the window (its ``counts``),
    beside ``window_fill``, the share of window positions that hold a
    token.  A net with expert layers counts ``expert_load (expert
    layers, experts held)``, the positions routed to each held expert
    this step, read here as the fullest expert's and the mean, and as
    ``held_pick_share``: of a layer's ``expert_picks`` (positions x
    experts per token), those that fell on held experts.  Whatever else
    a net counts goes on under the name the net gave it
    (``delta_retention``: the mean share of its state that a real
    position keeps, ``exp(g)``, over held heads and delta layers)."""
    counters = dict(counts, window_fill=episode_mask.mean())
    if "expert_load" in counters:
        load = counters.pop("expert_load").astype(jnp.float32)
        counters.update(
            expert_load_max=load.max(), expert_load_mean=load.mean(),
            held_pick_share=load.sum(-1).mean() / counters.pop("expert_picks"))
    return counters
