"""The training side of ``joyai_net``: ``trinity_training``'s (whole
token sequences, columns without a mask, the gradient summed a sequence
at a time, Adam's moments made again from the gradients that wait on
the host) with the next-next-token module's term beside the RL loss.

    term = NEXTN_WEIGHT * mean over the positions t that hold a real
           token at t + 1 and t + 2 of -log softmax(module_t)[token_{t+2}]

ASSUMED, each: the weight 0.1 (the family's own late in its training;
the config gives none); the term is taken over prompt and answer
positions alike; its gradient is not stopped at the trunk's last state
(it reaches the trunk, the embedding from two places and the head from
two places); a window's rows are whole episodes from position 0, so the
token at ``t + 1`` is the window's own observation one row on and the
target two rows on.  The loss of a batch is a sum over its rows, so the
term is each row's mean, summed.  It imports nothing of the program.

The memory plan is ``trinity_training``'s at 680 M parameters and one
sequence of 8,192 positions a step: two vocabulary-wide logit arrays
(8,192 x 16,160 float32, 0.5 GB a copy) where it had one.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import trinity_training
from .training import (  # noqa: F401  (the harness reaches them here)
    BASE_LR, GRAD_CLIP_NORM, draw, global_norm)
from .trinity_training import (  # noqa: F401
    _leaf_step, episode_columns, gather)

NEXTN_WEIGHT = 0.1


class _Computed:
    """``trinity_training.loss`` asks its net for a sequence's heads:
    this one hands it heads computed already."""

    def __init__(self, out):
        self.out = out

    def sequence(self, params, tokens, lowp):
        return self.out


def nextn_term(logits, tokens, real):
    """``logits (T, vocab)``, the module's; ``tokens (T,)``; ``real
    (T,)``, which rows hold a token: ``(mean cross-entropy against the
    token two rows on over the rows that have one, their count)``."""
    there = real[2:]                # t + 2 real: t and t + 1 are too
    log_p = jax.nn.log_softmax(logits[:-2], -1)
    taken = jnp.take_along_axis(log_p, tokens[2:, None], -1)[:, 0]
    return (-(taken * there).sum() / jnp.maximum(there.sum(), 1),
            there.sum())


def loss(net, params, row, cfg, lowp=None):
    """``trinity_training.loss`` of ONE sequence and the module's term:
    ``(total, parts)``; ``parts["mtp"]`` is the term before its
    weight."""
    tokens = row["observation"][0, :, 0]
    out = net.sequence(params, tokens, lowp)
    total, parts = trinity_training.loss(
        _Computed(out), params, row, cfg, lowp)
    term, _ = nextn_term(out["mtp"], tokens,
                         row["episode_mask"][0, :, 0, 0])
    return total + NEXTN_WEIGHT * term, dict(parts, mtp=term)


def follow(net, params, batches, cfg, lowp=None):
    """``trinity_training.follow`` over this module's ``loss``: the
    gradient summed over the batch's sequences, Adam's moments made
    again each step from the earlier steps' gradients."""
    lr = (cfg.get("base_lr", BASE_LR)
          * cfg["batch_size"] * cfg["forward_steps"])
    grad = jax.jit(jax.value_and_grad(
        lambda p, row: loss(net, p, row, cfg, lowp), has_aux=True))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    leaf_step = jax.jit(_leaf_step)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    treedef = jax.tree.structure(params)
    seens, losses, scales = [], [], []      # seens[k][i]: step k, leaf i
    for step_idx, batch in enumerate(batches):
        total, parts, grads = 0.0, {}, None
        for b in range(len(batch["action"])):
            row = jax.tree.map(lambda a: a[b:b + 1], batch)
            (t, p), g = grad(params, row)
            total += float(t)
            parts = {k: parts.get(k, 0.0) + float(v) for k, v in p.items()}
            grads = g if grads is None else add(grads, g)
            del g
        norm = global_norm(grads)
        scale = jnp.where(norm < GRAD_CLIP_NORM, 1.0, GRAD_CLIP_NORM / norm)
        last = step_idx == len(batches) - 1
        new, seen_now = [], []
        for i, (p, g) in enumerate(zip(jax.tree.leaves(params),
                                       jax.tree.leaves(grads))):
            p, seen = leaf_step(p, g * scale,
                                tuple(earlier[i] for earlier in seens), lr)
            new.append(p)
            # the first is asked for; the last is never needed again
            if not last or not seens:
                seen_now.append(np.asarray(seen))
        del grads
        params = jax.tree.unflatten(treedef, new)
        seens.append(seen_now)
        losses.append(total)
        scales.append(abs(parts["p"]) + parts["v"]
                      + cfg["entropy_regularization"] * parts["ent"]
                      + NEXTN_WEIGHT * parts["mtp"])
    first = jax.tree.unflatten(treedef, seens[0])
    del seens       # before the final parameters come to the host
    return losses, first, jax.device_get(params), scales
