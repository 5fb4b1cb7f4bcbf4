"""The training side of ``trinity_net``: windows that are whole token
sequences, columns without a legal-action mask, the gradient summed a
sequence at a time.

It shares with ``training.py`` what does not differ: the ring's draw,
the Adam step and the global norm.  The loss is ``training.loss``'s
written for one sequence of one seat (every term there is a sum over
rows), with the targets' backward recursion as a scan (``td_lambda``).
It imports nothing of the program.

Memory plan, for the released chip (16 GB) beside a host of 40 GiB
of which the TPU runtime and the harness's own copies hold ~25 GB, at
706 M parameters; it changes no number.  On the device: the float32
parameters (2.8 GB), the gradient being summed (2.8 GB) and one
sequence's gradient (2.8 GB); ``trinity_net`` makes a layer's
activations and a block of attention scores again coming back, so one
sequence's logits (4,096 x 25,024 float32, 0.4 GB a copy) are the
largest thing beside them.  Adam's two moments are kept NOWHERE: the
gradient is clipped by its global norm first (so that ``adam_step`` on
one leaf, whose norm is then under the clip, is ``adam_step`` on the
tree), and each leaf's moments are made again from the gradients Adam
saw in the earlier steps, which wait on the host: the first (asked
for anyway) and the second, 5.6 GB where the moments were 5.6 GB
beside the first gradient's 2.8.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np

from .training import (  # noqa: F401  (draw: the harness reaches it here)
    ADAM_B1, ADAM_B2, BASE_LR, GRAD_CLIP_NORM, adam_step, draw, global_norm)


def episode_columns(episode):
    """Wire-format episode of ONE seat -> per-step arrays ``(T, 1,
    ...)``; no mask: the environment lists no legal actions."""
    moments = [m for blob in episode["moment"] for m in pickle.loads(blob)]
    (seat,) = moments[0]["observation"].keys()

    def column(key, default, dtype):
        return np.asarray(
            [[np.ravel(m[key][seat])[:1] if m[key][seat] is not None
              else [default]] for m in moments], dtype)

    return {
        "obs": column("observation", 0, np.int32)[..., 0],      # (T, 1)
        "prob": column("selected_prob", 1.0, np.float32),
        "act": column("action", 0, np.int32),
        "value": column("value", 0.0, np.float32),
        "reward": column("reward", 0.0, np.float32),
        "return": column("return", 0.0, np.float32),
        "tmask": np.asarray([[[m["selected_prob"][seat] is not None]]
                             for m in moments], np.float32),
        "omask": np.asarray([[[m["observation"][seat] is not None]]
                             for m in moments], np.float32),
        "outcome": np.asarray([[episode["outcome"][seat]]], np.float32),
        "length": len(moments),
        "total": int(episode["steps"]),
    }


def gather(columns, slots, starts, seat, forward_steps, burn_in,
           one_seat):
    """Whole sequences from position ``start`` (0: an episode is no
    longer than a window), padded past the episode's end."""
    rows = []
    for slot, start in zip(slots, starts):
        col = columns[slot]
        g = start - burn_in + np.arange(burn_in + forward_steps)
        valid = (g >= 0) & (g < col["length"])
        gi = np.clip(g, 0, col["length"] - 1)

        def take(a, pad):
            w = a[gi]
            return np.where(valid.reshape((-1,) + (1,) * (w.ndim - 1)),
                            w, pad)

        outcome = col["outcome"]                               # (1, 1)
        rows.append({
            "observation": take(col["obs"], 0),
            "selected_prob": take(col["prob"], 1.0),
            "action": take(col["act"], 0),
            "value": np.where((g >= col["length"])[:, None, None],
                              outcome[None], take(col["value"], 0.0)),
            "reward": take(col["reward"], 0.0),
            "return": take(col["return"], 0.0),
            "outcome": outcome[None],
            "episode_mask": valid[:, None, None].astype(np.float32),
            "turn_mask": take(col["tmask"], 0.0),
            "observation_mask": take(col["omask"], 0.0),
            "progress": np.where(
                valid, g.astype(np.float32) / np.float32(col["total"]),
                np.float32(1.0))[:, None],
        })
    return jax.tree.map(lambda *leaves: np.stack(leaves), *rows)


def td_lambda(values, last, lam):
    """``training._backward`` without reward or discount (the value's
    target): ``g[T-1] = last``, ``g[t] = (1 - lam[t+1]) * values[t+1] +
    lam[t+1] * g[t+1]``; ``values``, ``lam`` ``(T, 1)``.  The same
    recursion as a ``lax.scan``: at 4,096 steps the Python loop is
    33,000 instructions on one number each, and the chip's compiler
    spent a quarter of an hour on them."""
    def back(g, nxt):
        v_next, l_next = nxt
        g = (1.0 - l_next) * v_next + l_next * g
        return g, g

    _, earlier = jax.lax.scan(back, last, (values[1:], lam[1:]),
                              reverse=True)
    return jnp.concatenate([earlier, last[None]])


def loss(net, params, row, cfg, lowp=None):
    """``training.loss`` over ONE sequence of one seat whose every
    action is legal (``row``: a batch of one, ``(1, T, 1, ...)``):
    ``(total, parts)``, policy + value - entropy bonus, TD(lambda) on
    the value, the outcome its last target."""
    if (cfg["policy_target"], cfg["value_target"]) != ("TD", "TD"):
        raise NotImplementedError("this side follows TD targets only")
    seat = lambda key: row[key][0, :, 0]                    # noqa: E731
    emask, omask, tmask = (seat(k) for k in (
        "episode_mask", "observation_mask", "turn_mask"))    # (T, 1)
    out = net.sequence(params, seat("observation"), lowp)
    # nothing is taken off the logits: no action is illegal
    policy, value = out["policy"] * tmask, out["value"] * omask
    log_b = jnp.log(jnp.clip(seat("selected_prob"), 1e-16, 1.0)) * emask
    log_pi = jax.nn.log_softmax(policy, -1)
    log_t = jnp.take_along_axis(log_pi, seat("action"), -1) * emask
    rho = jnp.exp(jnp.clip(jax.lax.stop_gradient(log_t) - log_b, -20, 20))
    rho = jnp.clip(rho, 0.0, 1.0)

    outcome = row["outcome"][0, 0]                           # (1, 1)
    frozen = jax.lax.stop_gradient(value) * emask + outcome * (1 - emask)
    lam = cfg["lambda"] + (1.0 - cfg["lambda"]) * (1.0 - omask)
    t_value = td_lambda(frozen, outcome[0], lam)
    # there is no return head: its advantage is the recorded return
    advantage = rho * (t_value - frozen + seat("return"))

    parts = {"p": (-log_t * advantage * tmask).sum(),
             "v": (((value - t_value) ** 2) * omask).sum() / 2}
    p = jnp.exp(log_pi)
    entropy = -(p * jnp.clip(log_pi, -1e32, 0.0)).sum(-1) * tmask.sum(-1)
    parts["ent"] = entropy.sum()
    decay = 1.0 - row["progress"][0, :, 0] * (
        1.0 - cfg["entropy_regularization_decay"])
    total = (parts["p"] + parts["v"]
             - cfg["entropy_regularization"] * (entropy * decay).sum())
    return total, parts


def _leaf_step(p, g, earlier, lr):
    """``adam_step`` on one leaf whose moments are made again from the
    gradients Adam saw in the ``earlier`` steps (the same recurrence
    from zero, in the same order)."""
    mu = nu = jnp.zeros_like(p)
    for seen in earlier:
        mu = ADAM_B1 * mu + (1 - ADAM_B1) * seen
        nu = ADAM_B2 * nu + (1 - ADAM_B2) * seen * seen
    p, _, _, _, seen = adam_step(p, g, mu, nu, len(earlier), lr)
    return p, seen


def follow(net, params, batches, cfg, lowp=None):
    """``training.follow`` with the gradient summed over the batch's
    sequences and Adam's moments made again each step from the earlier
    steps' gradients, which wait on the host (the module's memory
    plan)."""
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
        scales.append(abs(parts["p"]) + parts.get("v", 0.0)
                      + parts.get("r", 0.0)
                      + cfg["entropy_regularization"] * parts["ent"])
    first = jax.tree.unflatten(treedef, seens[0])
    del seens       # before the final parameters come to the host
    return losses, first, jax.device_get(params), scales
