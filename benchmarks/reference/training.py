"""The learner's step, plain: draw -> window gather -> forward ->
targets -> loss -> gradient -> clip, weight decay, Adam.  Written from
HandyRL's train.py / losses.py semantics (summed losses, importance
ratios clipped at 1, terminal bootstrap, entropy regularisation decayed
by episode progress, two-player value symmetrisation) and from this
repo's ring contract (triangular recency draw, uniform window and seat),
in float32 at precision ``highest``, with Python loops where the program
scans.  It imports nothing of the program and is handed nothing the
program made: episodes and initial weights come from the benchmark.
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np

ILLEGAL = np.float32(1e32)
GRAD_CLIP_NORM = 4.0
WEIGHT_DECAY = 1e-5
BASE_LR = 3e-8
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# -- episodes ---------------------------------------------------------

def episode_columns(episode):
    """Wire-format episode -> per-step arrays over (T, P, ...): what an
    actor recorded, with the defaults a missing entry stands for
    (probability 1, action 0, every action illegal, zeros)."""
    moments = [m for blob in episode["moment"] for m in pickle.loads(blob)]
    players = list(moments[0]["observation"].keys())
    first = moments[0]["turn"][0]
    template = jax.tree.map(np.zeros_like, moments[0]["observation"][first])
    actions = len(moments[0]["action_mask"][first])

    def stack(get, default, dtype):
        return np.asarray(
            [[get(m, p) if get(m, p) is not None else default
              for p in players] for m in moments], dtype)

    obs_rows = [[m["observation"][p] if m["observation"][p] is not None
                 else template for p in players] for m in moments]
    obs = jax.tree.map(
        lambda *leaves: np.asarray(leaves, np.float32).reshape(
            (len(moments), len(players)) + np.shape(leaves[0])),
        *[o for row in obs_rows for o in row])
    scalar = lambda key: stack(  # noqa: E731
        lambda m, p: None if m[key][p] is None
        else np.ravel(m[key][p])[:1], [0.0], np.float32)
    return {
        "obs": obs,
        "prob": stack(lambda m, p: None if m["selected_prob"][p] is None
                      else [m["selected_prob"][p]], [1.0], np.float32),
        "act": stack(lambda m, p: None if m["action"][p] is None
                     else [m["action"][p]], [0], np.int32),
        "illegal": stack(
            lambda m, p: None if m["action_mask"][p] is None
            else np.asarray(m["action_mask"][p]) != 0,
            np.ones(actions, bool), bool),
        "value": scalar("value"), "reward": scalar("reward"),
        "return": scalar("return"),
        "tmask": stack(lambda m, p: [m["selected_prob"][p] is not None],
                       None, np.float32),
        "omask": stack(lambda m, p: [m["observation"][p] is not None],
                       None, np.float32),
        "outcome": np.asarray([[episode["outcome"][p]] for p in players],
                              np.float32),
        "length": len(moments),
        "total": int(episode["steps"]),
    }


# -- draw and gather --------------------------------------------------

def draw(seed, step_idx, size, oldest, capacity, lengths, batch_size,
         forward_steps, seats):
    """Rows of one step: triangular recency over the ring's ``size``
    live episodes, a uniform window start, a uniform seat (``seats`` 0:
    every seat trains).  The program keys its draw by (config seed, step
    counter); so does this, on the same device, so that both floor the
    same float32."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step_idx)
    k1, k2, k3 = jax.random.split(key, 3)
    n = jnp.float32(size)
    u = jax.random.uniform(k1, (batch_size,))
    idx = jnp.floor(
        (jnp.sqrt(1.0 + 4.0 * u * n * (n + 1)) - 3.0) / 2.0
    ).astype(jnp.int32) + 1
    idx = jnp.clip(idx, 0, size - 1)
    slots = (oldest + idx) % capacity
    cands = 1 + jnp.maximum(0, jnp.asarray(lengths)[slots] - forward_steps)
    starts = jnp.floor(
        jax.random.uniform(k2, (batch_size,)) * cands).astype(jnp.int32)
    if seats:
        seat = jax.random.randint(k3, (batch_size,), 0, seats, jnp.int32)
    else:
        seat = jnp.zeros(batch_size, jnp.int32)
    return np.asarray(slots), np.asarray(starts), np.asarray(seat)


def gather(columns, slots, starts, seat, forward_steps, burn_in,
           one_seat):
    """The training batch of the drawn rows, from the episodes
    themselves: a window of ``burn_in + forward_steps`` steps, padded
    where it runs past either end of the episode."""
    t_win = burn_in + forward_steps
    rows = []
    for slot, start, s in zip(slots, starts, seat):
        col = columns[slot]
        g = start - burn_in + np.arange(t_win)
        valid = (g >= 0) & (g < col["length"])
        after = g >= col["length"]
        gi = np.clip(g, 0, col["length"] - 1)
        players = [s] if one_seat else list(range(col["prob"].shape[1]))

        def take(a, pad):
            w = a[gi][:, players]
            m = valid.reshape((-1,) + (1,) * (w.ndim - 1))
            return np.where(m, w, pad)

        outcome = col["outcome"][players]                     # (P, 1)
        rows.append({
            "observation": jax.tree.map(
                lambda a: take(a, 0).astype(np.float32), col["obs"]),
            "selected_prob": take(col["prob"], 1.0),
            "action": take(col["act"], 0),
            "action_mask": np.where(take(col["illegal"], True),
                                    ILLEGAL, np.float32(0)),
            "value": np.where(after[:, None, None], outcome[None],
                              take(col["value"], 0.0)),
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


# -- forward over the window ------------------------------------------

def predict(net, params, batch, cfg, lowp):
    """Net outputs over a (B, T, P, ...) batch, masked as the loss
    reads them: policy logits minus the illegal-action mask on acting
    steps, scalar heads zeroed where the player did not observe."""
    obs = batch["observation"]
    B, T, P = batch["action"].shape[:3]
    if not net.RECURRENT:
        flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[3:]), obs)
        out = net.forward(params, flat, None, lowp)
        out = {k: v.reshape((B, T, P) + v.shape[1:])
               for k, v in out.items()}
    else:
        hidden = net.init_hidden((B, P))
        steps = []
        for t in range(T):
            omask = batch["observation_mask"][:, t]            # (B, P, 1)

            def like(h):
                return omask.reshape(omask.shape[:2] + (1,) * (h.ndim - 2))

            h_in = jax.tree.map(
                lambda h: (h * like(h)).reshape((-1,) + h.shape[2:]),
                hidden)
            o_t = jax.tree.map(
                lambda a: a[:, t].reshape((-1,) + a.shape[3:]), obs)
            out = net.forward(params, o_t, h_in, lowp)
            nxt = jax.tree.map(
                lambda h: h.reshape((B, P) + h.shape[1:]),
                out.pop("hidden"))
            out = {k: v.reshape((B, P) + v.shape[1:])
                   for k, v in out.items()}
            if t < cfg["burn_in_steps"]:       # burn-in: no gradient
                out = jax.lax.stop_gradient(out)
                nxt = jax.lax.stop_gradient(nxt)
            hidden = jax.tree.map(
                lambda h, n: h * (1 - like(h)) + n * like(h), hidden, nxt)
            steps.append(out)
        out = {k: jnp.stack([s[k] for s in steps], 1) for k in steps[0]}
    result = {}
    for k, o in out.items():
        if k == "policy":
            result[k] = o * batch["turn_mask"] - batch["action_mask"]
        else:
            result[k] = o * batch["observation_mask"]
    return result


# -- targets ----------------------------------------------------------

def _backward(values, returns, rewards, lam, gamma, upgo):
    """TD(lambda) or UPGO targets by the backward recursion over time
    (axis 1); the last step's target is the recorded return."""
    T = values.shape[1]
    rewards = jnp.zeros_like(values) if rewards is None else rewards
    g = returns[:, -1]
    out = [g]
    for t in range(T - 2, -1, -1):
        v_next, l_next = values[:, t + 1], lam[:, t + 1]
        blend = (1.0 - l_next) * v_next + l_next * g
        g = rewards[:, t] + gamma * (
            jnp.maximum(v_next, blend) if upgo else blend)
        out.append(g)
    return jnp.stack(out[::-1], 1)


def target(algorithm, values, returns, rewards, lmb, gamma, masks):
    if values is None:
        return returns, returns
    if algorithm == "MC":
        return returns, returns - values
    lam = lmb + (1.0 - lmb) * (1.0 - masks)
    if algorithm not in ("TD", "UPGO"):
        raise NotImplementedError(algorithm)
    t = _backward(values, returns, rewards, lam, gamma, algorithm == "UPGO")
    return t, t - values


# -- loss -------------------------------------------------------------

def _huber(x):
    a = jnp.abs(x)
    return jnp.where(a < 1.0, 0.5 * x * x, a - 0.5)


def loss(net, params, batch, cfg, lowp=None):
    """(total, parts): policy + value (+ return) - entropy bonus,
    summed over the batch, on the steps after burn-in."""
    out = predict(net, params, batch, cfg, lowp)
    b = cfg["burn_in_steps"]
    if b:
        batch = {k: (v[:, b:] if k != "observation" and v.shape[1] > 1
                     else v) for k, v in batch.items()}
        out = {k: v[:, b:] for k, v in out.items()}
    emask, omask = batch["episode_mask"], batch["observation_mask"]
    tmask = batch["turn_mask"]
    log_b = jnp.log(jnp.clip(batch["selected_prob"], 1e-16, 1.0)) * emask
    log_pi = jax.nn.log_softmax(out["policy"], -1)
    log_t = jnp.take_along_axis(log_pi, batch["action"], -1) * emask
    rho = jnp.exp(jnp.clip(jax.lax.stop_gradient(log_t) - log_b, -20, 20))
    rho = jnp.clip(rho, 0.0, 1.0)

    frozen = {k: jax.lax.stop_gradient(v) for k, v in out.items()}
    vmask = omask
    if "value" in frozen:
        v = frozen["value"]
        if cfg["turn_based_training"] and v.shape[2] == 2:
            v_opp, o_opp = -jnp.flip(v, 2), jnp.flip(omask, 2)
            v = (v * omask + v_opp * o_opp) / (omask + o_opp + 1e-8)
            vmask = jnp.clip(omask + o_opp, 0.0, 1.0)
        frozen["value"] = v * emask + batch["outcome"] * (1 - emask)

    lmb, gamma = cfg["lambda"], cfg["gamma"]
    v_args = (frozen.get("value"), batch["outcome"], None, lmb, 1.0, vmask)
    r_args = (frozen.get("return"), batch["return"], batch["reward"],
              lmb, gamma, omask)
    t_value, a_value = target(cfg["value_target"], *v_args)
    t_return, a_return = target(cfg["value_target"], *r_args)
    if cfg["policy_target"] != cfg["value_target"]:
        _, a_value = target(cfg["policy_target"], *v_args)
        _, a_return = target(cfg["policy_target"], *r_args)
    advantage = rho * (a_value + a_return)

    parts = {"p": (-log_t * advantage * tmask).sum()}
    if "value" in out:
        parts["v"] = (((out["value"] - t_value) ** 2) * omask).sum() / 2
    if "return" in out:
        parts["r"] = (_huber(out["return"] - t_return) * omask).sum()
    p = jnp.exp(log_pi)
    entropy = -(p * jnp.clip(log_pi, -1e32, 0.0)).sum(-1) * tmask.sum(-1)
    parts["ent"] = entropy.sum()
    decay = 1.0 - batch["progress"] * (
        1.0 - cfg["entropy_regularization_decay"])
    total = (parts["p"] + parts.get("v", 0.0) + parts.get("r", 0.0)
             - cfg["entropy_regularization"] * (entropy * decay).sum())
    return total, parts


# -- optimiser --------------------------------------------------------

def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(tree)))


def adam_step(params, grads, mu, nu, count, lr):
    """Clip the gradient to global norm 4, add coupled weight decay,
    Adam with bias correction, step by ``lr``.  Returns the new state
    and the gradient as Adam was handed it."""
    norm = global_norm(grads)
    scale = jnp.where(norm < GRAD_CLIP_NORM, 1.0, GRAD_CLIP_NORM / norm)
    seen = jax.tree.map(lambda g, p: g * scale + WEIGHT_DECAY * p,
                        grads, params)
    count = count + 1
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, seen)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      nu, seen)
    c1, c2 = 1 - ADAM_B1 ** count, 1 - ADAM_B2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return params, mu, nu, count, seen


def follow(net, params, batches, cfg, lowp=None):
    """Drive ``len(batches)`` steps from ``params``.  Returns per-step
    losses, the first gradient as Adam saw it, the final params, and
    per step the size of the loss's parts (|policy| + value + return +
    the entropy bonus at most): the total is a difference of those and
    can pass through zero, so a gap in it is measured against this."""
    lr = (cfg.get("base_lr", BASE_LR)
          * cfg["batch_size"] * cfg["forward_steps"])
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: loss(net, p, b, cfg, lowp), has_aux=True))
    step = jax.jit(adam_step)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count, losses, scales, first = 0, [], [], None
    for batch in batches:
        (total, parts), grads = grad(params, batch)
        params, mu, nu, count, seen = step(params, grads, mu, nu, count, lr)
        losses.append(float(total))
        scales.append(float(
            abs(parts["p"]) + parts.get("v", 0.0) + parts.get("r", 0.0)
            + cfg["entropy_regularization"] * parts["ent"]))
        if first is None:
            first = jax.device_get(seen)
    return losses, first, jax.device_get(params), scales
