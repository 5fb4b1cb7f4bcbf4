"""Value-target / advantage estimators as backward recursions over time.

Semantic parity with /root/reference/handyrl/losses.py:16-81 (Monte
Carlo, TD(lambda), UPGO, V-Trace per IMPALA, arXiv:1802.01561).  Every
estimator here is a recursion ``G_t = f_t(G_{t+1})`` from the window's
last moment back to its first, and every ``f_t`` is a map closed under
composition: ``x -> a x + b`` (TD(lambda), V-Trace's correction) or
``x -> max(c, a x + b)`` with ``a >= 0`` (UPGO).  One recursion, two
schedules, chosen at trace time by the STATIC length of the time axis
(``LOG_DEPTH_ABOVE``; no option selects it):

  * short (a board game's 8 moments): the reference's reverse Python
    loop as one reverse ``lax.scan`` — one fused XLA loop of T - 1
    dependent iterations instead of T dispatches.  An iteration costs
    its own latency, 35 ns to 2 us on a v5e by how the compiler states
    the body (PERF.md, PR 46): nothing at 7, and 14 ms a step at a
    sequence window's 8,191;
  * long (a sequence policy's 4,096 or 8,192 moments): the maps of
    every suffix composed by ``lax.associative_scan`` along the time
    axis, log2(T) levels deep with time on the minor (lane) axis, and
    each composed map applied to the seed.  The same numbers to
    rounding (~1e-7 at unit scale), float32 throughout.

Array layout: ``(B, T, P, 1)`` (batch, time, player, channel), time on
axis 1 — identical to the reference's batch layout.  All functions are
jit-safe and differentiable (inputs are expected pre-``stop_gradient``
where the algorithm calls for it, as in the reference, which computes
targets on detached values).
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
from jax import lax

# The longest time axis (T - 1 recursion steps) still walked one moment
# at a time.  Any value from 12 to 4,094 sorts today's configurations
# the same way (7 steps against 4,095 and 8,191), each to its faster
# form on a v5e (PERF.md, PR 46): at a board game's 1,024 rows walking
# won at every length read (2.3 us against 9.2 at 8 moments), at a
# sequence window's one or two rows composing did (0.105 ms against
# 20.06 at one row of 8,191; 0.064 against 0.166 at two of 4,095).
LOG_DEPTH_ABOVE = 64

_noted = threading.local()


@contextlib.contextmanager
def noting():
    """Collect ``{"form", "length"}`` of every recursion TRACED on this
    thread inside the block: what a step's compile record says of the
    schedule its targets took (``Trainer.targets_scan``)."""
    outer = getattr(_noted, "notes", None)
    _noted.notes = notes = []
    try:
        yield notes
    finally:
        _noted.notes = outer


def _time_leading(x):
    return jnp.moveaxis(x, 1, 0)


def _time_second(x):
    return jnp.moveaxis(x, 0, 1)


def _reverse_scan(step_fn, init, xs_time_second):
    """Run ``step_fn`` backward over axis-1 slices and re-stack outputs
    in forward time order."""
    xs = jax.tree.map(_time_leading, xs_time_second)
    _, ys = lax.scan(step_fn, init, xs, reverse=True)
    return _time_second(ys)


def _compose(later, earlier):
    """``earlier`` after ``later``: the map of a longer suffix.  Pairs
    ``(a, b)`` are ``x -> a x + b``; triples ``(a, b, c)`` are
    ``x -> max(c, a x + b)``, closed under composition because
    ``a >= 0``.  An affine recursion carries PAIRS: stated as a triple
    with ``c = -inf`` it reads ``0 * -inf = nan`` once the running
    product ``a`` underflows."""
    a, b, *floor = earlier
    composed = [a * later[0], a * later[1] + b]
    if floor:
        composed.append(jnp.maximum(floor[0], a * later[2] + b))
    return tuple(composed)


def _composed_scan(maps, init):
    """Every suffix's composed map applied to ``init``, log-depth: time
    goes to the minor axis (rows of ``(B * P, T - 1)``, each level one
    lane-dense elementwise pass) and comes back to axis 1."""
    lead = maps[0].shape[:1] + maps[0].shape[2:]
    rows = [jnp.moveaxis(m, 1, -1).reshape(-1, m.shape[1]) for m in maps]
    a, b, *floor = lax.associative_scan(_compose, rows, reverse=True, axis=1)
    out = a * init.reshape(-1, 1) + b
    if floor:
        out = jnp.maximum(floor[0], out)
    return jnp.moveaxis(out.reshape(lead + out.shape[1:]), -1, 1)


def _backward(step_fn, init, xs, maps_fn):
    """``G_t = f_t(G_{t+1})`` over axis-1 slices of ``xs`` with
    ``G_{T-1} = init``, in forward time order with ``init`` appended:
    ``step_fn`` walks it, ``maps_fn(*xs)`` states each ``f_t`` for
    ``_compose``; the axis's static length picks the schedule."""
    length = xs[0].shape[1]
    log_depth = length > LOG_DEPTH_ABOVE
    notes = getattr(_noted, "notes", None)
    if notes is not None:
        notes.append({"form": "log_depth" if log_depth else "sequential",
                      "length": length})
    if log_depth:
        ys = _composed_scan(maps_fn(*xs), init)
    else:
        ys = _reverse_scan(step_fn, init, xs)
    return jnp.concatenate([ys, init[:, None]], axis=1)


def monte_carlo(values, returns):
    """Targets are the observed returns themselves."""
    return returns, returns - values


def temporal_difference(values, returns, rewards, lambda_, gamma):
    """TD(lambda) targets via backward recursion:

      G_t = r_t + gamma * ((1 - lambda_{t+1}) * V_{t+1} + lambda_{t+1} * G_{t+1})

    with ``G_{T-1} = returns_{T-1}``.
    """
    rewards = jnp.zeros_like(values) if rewards is None else rewards

    def step(g_next, x):
        v_next, r, lam = x
        g = r + gamma * ((1.0 - lam) * v_next + lam * g_next)
        return g, g

    def maps(v_next, r, lam):
        return gamma * lam, r + gamma * (1.0 - lam) * v_next

    targets = _backward(
        step,
        returns[:, -1],
        (values[:, 1:], rewards[:, :-1], lambda_[:, 1:]),
        maps,
    )
    return targets, targets - values


def upgo(values, returns, rewards, lambda_, gamma):
    """UPGO targets: bootstrap through the better of the next value and
    the lambda-blended continuation (only propagates advantages along
    trajectories that outperformed the baseline)."""
    rewards = jnp.zeros_like(values) if rewards is None else rewards

    def step(g_next, x):
        v_next, r, lam = x
        g = r + gamma * jnp.maximum(v_next, (1.0 - lam) * v_next + lam * g_next)
        return g, g

    def maps(v_next, r, lam):
        # gamma >= 0 takes the max through the sum: the walked step is
        # max(r + gamma V, r + gamma ((1 - lam) V + lam G))
        return (gamma * lam, r + gamma * (1.0 - lam) * v_next,
                r + gamma * v_next)

    targets = _backward(
        step,
        returns[:, -1],
        (values[:, 1:], rewards[:, :-1], lambda_[:, 1:]),
        maps,
    )
    return targets, targets - values


def vtrace(values, returns, rewards, lambda_, gamma, rhos, cs):
    """V-Trace targets and advantages (IMPALA, arXiv:1802.01561).

    ``rhos``/``cs`` are the clipped importance ratios; the correction
    term ``vs - V`` accumulates backward scaled by ``gamma * lambda * c``.
    """
    rewards = jnp.zeros_like(values) if rewards is None else rewards
    values_next = jnp.concatenate([values[:, 1:], returns[:, -1:]], axis=1)
    deltas = rhos * (rewards + gamma * values_next - values)

    def step(acc, x):
        delta, lam, c = x
        acc = delta + gamma * lam * c * acc
        return acc, acc

    def maps(delta, lam, c):
        return gamma * lam * c, delta

    vs_minus_v = _backward(
        step,
        deltas[:, -1],
        (deltas[:, :-1], lambda_[:, 1:], cs[:, :-1]),
        maps,
    )
    vs = vs_minus_v + values
    vs_next = jnp.concatenate([vs[:, 1:], returns[:, -1:]], axis=1)
    advantages = rewards + gamma * vs_next - values
    return vs, advantages


def impact(values, returns, rewards, lambda_, gamma, rhos, cs):
    """IMPACT targets (arXiv:1912.00167): the V-Trace recursion driven
    by TARGET-NETWORK importance ratios.

    The estimator is numerically the V-Trace recursion — what the
    IMPACT scheme changes is which policy produced ``rhos``/``cs``
    (the maintained target policy instead of the live learner policy;
    see ops.losses) and how the policy loss consumes the advantages (a
    two-sided surrogate clip).  Kept as its own dispatch entry so a
    ``value_target: IMPACT`` config reads explicitly and the golden
    tests can pin the identity."""
    return vtrace(values, returns, rewards, lambda_, gamma, rhos, cs)


def compute_target(algorithm: str, values, returns, rewards, lmb, gamma,
                   rhos, cs, masks):
    """Dispatch to a target estimator, blending lambda with the
    observation mask (unobserved steps pass through with lambda = 1),
    exactly as /root/reference/handyrl/losses.py:63-81."""
    if values is None:
        # no baseline head: fall back to Monte-Carlo returns
        return returns, returns

    if algorithm == "MC":
        return monte_carlo(values, returns)

    lambda_ = lmb + (1.0 - lmb) * (1.0 - masks)

    if algorithm == "TD":
        return temporal_difference(values, returns, rewards, lambda_, gamma)
    if algorithm == "UPGO":
        return upgo(values, returns, rewards, lambda_, gamma)
    if algorithm == "VTRACE":
        return vtrace(values, returns, rewards, lambda_, gamma, rhos, cs)
    if algorithm == "IMPACT":
        return impact(values, returns, rewards, lambda_, gamma, rhos, cs)
    raise ValueError(f"unknown target algorithm {algorithm!r}")
