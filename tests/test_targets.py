"""Golden tests for the RL target estimators.

Each scan implementation is checked against an independent numpy
reference written directly from the recurrences in
/root/reference/handyrl/losses.py:16-61, plus hand-computed tiny
sequences and algebraic identities.
"""

import numpy as np
import pytest

from handyrl_tpu.ops import targets as targets_module
from handyrl_tpu.ops import (
    compute_target,
    impact,
    monte_carlo,
    temporal_difference,
    upgo,
    vtrace,
)

B, T, P = 3, 7, 2
RNG = np.random.default_rng(42)


def _rand(shape=(B, T, P, 1)):
    return RNG.normal(size=shape).astype(np.float32)


def _np_td(values, returns, rewards, lambda_, gamma):
    T = values.shape[1]
    tgt = np.zeros_like(values)
    tgt[:, -1] = returns[:, -1]
    for i in range(T - 2, -1, -1):
        lam = lambda_[:, i + 1]
        tgt[:, i] = rewards[:, i] + gamma * (
            (1 - lam) * values[:, i + 1] + lam * tgt[:, i + 1]
        )
    return tgt


def _np_upgo(values, returns, rewards, lambda_, gamma):
    T = values.shape[1]
    tgt = np.zeros_like(values)
    tgt[:, -1] = returns[:, -1]
    for i in range(T - 2, -1, -1):
        lam = lambda_[:, i + 1]
        v = values[:, i + 1]
        tgt[:, i] = rewards[:, i] + gamma * np.maximum(
            v, (1 - lam) * v + lam * tgt[:, i + 1]
        )
    return tgt


def _np_vtrace(values, returns, rewards, lambda_, gamma, rhos, cs):
    T = values.shape[1]
    v_next = np.concatenate([values[:, 1:], returns[:, -1:]], axis=1)
    deltas = rhos * (rewards + gamma * v_next - values)
    vmv = np.zeros_like(values)
    vmv[:, -1] = deltas[:, -1]
    for i in range(T - 2, -1, -1):
        vmv[:, i] = deltas[:, i] + gamma * lambda_[:, i + 1] * cs[:, i] * vmv[:, i + 1]
    vs = vmv + values
    vs_next = np.concatenate([vs[:, 1:], returns[:, -1:]], axis=1)
    adv = rewards + gamma * vs_next - values
    return vs, adv


def test_monte_carlo():
    values, returns = _rand(), _rand()
    tgt, adv = monte_carlo(values, returns)
    np.testing.assert_allclose(tgt, returns)
    np.testing.assert_allclose(adv, returns - values)


@pytest.mark.parametrize("gamma", [1.0, 0.9])
def test_td_matches_reference_recurrence(gamma):
    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    tgt, adv = temporal_difference(values, returns, rewards, lambda_, gamma)
    expect = _np_td(values, returns, rewards, lambda_, gamma)
    np.testing.assert_allclose(tgt, expect, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(adv, expect - values, rtol=1e-5, atol=1e-6)


def test_td_hand_computed():
    # B=1, T=3, P=1: V=[0.5, 1.0, 2.0], r=[1, 2, -], lam=1, gamma=0.5
    # G2 = ret2 = 4;  G1 = 2 + .5*4 = 4;  G0 = 1 + .5*4 = 3
    values = np.array([0.5, 1.0, 2.0], np.float32).reshape(1, 3, 1, 1)
    rewards = np.array([1.0, 2.0, 0.0], np.float32).reshape(1, 3, 1, 1)
    returns = np.full((1, 3, 1, 1), 4.0, np.float32)
    lambda_ = np.ones((1, 3, 1, 1), np.float32)
    tgt, _ = temporal_difference(values, returns, rewards, lambda_, 0.5)
    np.testing.assert_allclose(
        np.asarray(tgt).ravel(), [3.0, 4.0, 4.0], rtol=1e-6
    )


def test_td_lambda0_is_one_step_bootstrap():
    values, returns = _rand(), _rand()
    rewards = _rand()
    lambda_ = np.zeros((B, T, P, 1), np.float32)
    gamma = 0.9
    tgt, _ = temporal_difference(values, returns, rewards, lambda_, gamma)
    expect = rewards[:, :-1] + gamma * values[:, 1:]
    np.testing.assert_allclose(tgt[:, :-1], expect, rtol=1e-5, atol=1e-6)


def test_upgo_matches_reference_recurrence():
    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    tgt, adv = upgo(values, returns, rewards, lambda_, 0.95)
    expect = _np_upgo(values, returns, rewards, lambda_, 0.95)
    np.testing.assert_allclose(tgt, expect, rtol=1e-5, atol=1e-6)


def test_upgo_dominates_td():
    """UPGO bootstraps through max(V, blend) so its targets are >= TD's."""
    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    td_tgt, _ = temporal_difference(values, returns, rewards, lambda_, 0.9)
    up_tgt, _ = upgo(values, returns, rewards, lambda_, 0.9)
    assert np.all(np.asarray(up_tgt) >= np.asarray(td_tgt) - 1e-5)


def test_vtrace_matches_reference_recurrence():
    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    rhos = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    cs = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    vs, adv = vtrace(values, returns, rewards, lambda_, 0.9, rhos, cs)
    evs, eadv = _np_vtrace(values, returns, rewards, lambda_, 0.9, rhos, cs)
    np.testing.assert_allclose(vs, evs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(adv, eadv, rtol=1e-5, atol=1e-6)


def test_vtrace_on_policy_reduces_to_td():
    """With rho = c = 1 in the outcome channel (zero rewards, gamma = 1,
    returns tiled from the final outcome), V-Trace targets equal
    TD(lambda) targets — the off-policy correction vanishes."""
    values = _rand()
    returns = np.tile(_rand((B, 1, P, 1)), (1, T, 1, 1))
    rewards = np.zeros((B, T, P, 1), np.float32)
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    ones = np.ones((B, T, P, 1), np.float32)
    vs, _ = vtrace(values, returns, rewards, lambda_, 1.0, ones, ones)
    td_tgt, _ = temporal_difference(values, returns, rewards, lambda_, 1.0)
    np.testing.assert_allclose(vs, td_tgt, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rho_clip,c_clip", [(1.3, 1.1), (2.0, 1.0),
                                             (0.5, 0.5)])
def test_vtrace_nonunit_clips_match_reference(rho_clip, c_clip):
    """V-Trace under NON-UNIT clips: ratios drawn in [0, 2] and clipped
    at the configured rho/c ceilings (the `rho_clip`/`c_clip` config
    keys) still match the reference recurrence exactly — the recursion
    is clip-agnostic, the clips live in what the caller feeds it."""
    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    raw = RNG.uniform(0, 2, size=(B, T, P, 1)).astype(np.float32)
    rhos = np.clip(raw, 0.0, rho_clip)
    cs = np.clip(raw, 0.0, c_clip)
    vs, adv = vtrace(values, returns, rewards, lambda_, 0.9, rhos, cs)
    evs, eadv = _np_vtrace(values, returns, rewards, lambda_, 0.9,
                           rhos, cs)
    np.testing.assert_allclose(vs, evs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(adv, eadv, rtol=1e-5, atol=1e-6)


def test_impact_is_vtrace_with_target_ratios():
    """The IMPACT target path is the V-Trace recursion — identical
    outputs on identical inputs (what changes in the impact scheme is
    WHICH policy produced the ratios, which happens in ops.losses);
    also reachable through the compute_target dispatch as "IMPACT"."""
    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = RNG.uniform(0, 1, size=(B, T, P, 1)).astype(np.float32)
    raw = RNG.uniform(0, 2, size=(B, T, P, 1)).astype(np.float32)
    rhos = np.clip(raw, 0.0, 1.3)
    cs = np.clip(raw, 0.0, 1.0)
    vs_i, adv_i = impact(values, returns, rewards, lambda_, 0.9,
                         rhos, cs)
    vs_v, adv_v = vtrace(values, returns, rewards, lambda_, 0.9,
                         rhos, cs)
    np.testing.assert_array_equal(np.asarray(vs_i), np.asarray(vs_v))
    np.testing.assert_array_equal(np.asarray(adv_i), np.asarray(adv_v))

    masks = np.ones((B, T, P, 1), np.float32)
    vs_d, adv_d = compute_target("IMPACT", values, returns, rewards,
                                 0.7, 0.9, rhos, cs, masks)
    evs, eadv = _np_vtrace(
        values, returns, rewards,
        np.full((B, T, P, 1), 0.7, np.float32), 0.9, rhos, cs)
    np.testing.assert_allclose(vs_d, evs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(adv_d, eadv, rtol=1e-5, atol=1e-6)


def test_compute_target_mask_blend():
    """masks=0 forces lambda to 1 regardless of configured lambda."""
    values, returns, rewards = _rand(), _rand(), _rand()
    masks = np.zeros((B, T, P, 1), np.float32)
    tgt, _ = compute_target("TD", values, returns, rewards, 0.3, 0.9,
                            None, None, masks)
    ones = np.ones((B, T, P, 1), np.float32)
    expect = _np_td(values, returns, rewards, ones, 0.9)
    np.testing.assert_allclose(tgt, expect, rtol=1e-5, atol=1e-6)


def test_compute_target_no_baseline():
    returns = _rand()
    tgt, adv = compute_target("VTRACE", None, returns, None, 0.7, 0.9,
                              None, None, None)
    np.testing.assert_allclose(tgt, returns)
    np.testing.assert_allclose(adv, returns)


def test_targets_jit_and_grad():
    """Estimators must be jittable and differentiable end-to-end."""
    import jax
    import jax.numpy as jnp

    values, returns, rewards = _rand(), _rand(), _rand()
    lambda_ = np.full((B, T, P, 1), 0.7, np.float32)

    @jax.jit
    def loss(v):
        tgt, adv = temporal_difference(v, returns, rewards, lambda_, 0.9)
        return jnp.sum(adv ** 2)

    g = jax.grad(loss)(jnp.asarray(values))
    assert np.all(np.isfinite(np.asarray(g)))


# -- one recursion, two schedules ---------------------------------------
#
# The recursion is walked one moment at a time where the time axis is
# short and composed log-depth where it is long (ops/targets.py).  Both
# forms are held to each other and to the numpy recurrences above at
# every length; which one runs is decided by the static length alone.

CONSTANT = targets_module.LOG_DEPTH_ABOVE
FORMS = {"sequential": 10 ** 9, "log_depth": 0}
# float32, ~13 levels of composition, targets a few units large
TOL = dict(rtol=1e-5, atol=1e-5)


def _inputs(seed, b, t, p):
    """Values in [-1, 1], 70% of moments observed (the rest switch
    lambda to 1), importance ratios clipped at non-unit ceilings."""
    rng = np.random.default_rng(seed)
    shape = (b, t, p, 1)

    def unit():
        return rng.uniform(-1, 1, size=shape).astype(np.float32)

    raw = rng.uniform(0, 2, size=shape).astype(np.float32)
    return {"values": unit(), "returns": unit(), "rewards": unit(),
            "masks": (rng.uniform(size=shape) < 0.7).astype(np.float32),
            "rhos": np.clip(raw, 0.0, 1.3), "cs": np.clip(raw, 0.0, 1.1)}


def _both_forms(monkeypatch, algorithm, x, lmb, gamma):
    out = {}
    for form, constant in FORMS.items():
        monkeypatch.setattr(targets_module, "LOG_DEPTH_ABOVE", constant)
        with targets_module.noting() as notes:
            out[form] = compute_target(
                algorithm, x["values"], x["returns"], x["rewards"], lmb,
                gamma, x["rhos"], x["cs"], x["masks"])
        assert notes == [{"form": form,
                          "length": x["values"].shape[1] - 1}]
    return out


def _reference(algorithm, x, lmb, gamma):
    lambda_ = lmb + (1.0 - lmb) * (1.0 - x["masks"])
    common = (x["values"], x["returns"], x["rewards"], lambda_, gamma)
    if algorithm == "TD":
        return _np_td(*common)
    if algorithm == "UPGO":
        return _np_upgo(*common)
    return _np_vtrace(*common, x["rhos"], x["cs"])[0]


@pytest.mark.parametrize("gamma", [1.0, 0.8])
@pytest.mark.parametrize("rows", [(1, 1), (2, 1), (2, 4)],
                         ids=["rows1", "rows2", "rows8"])
@pytest.mark.parametrize("t", [2, 3, 8, CONSTANT, CONSTANT + 1, 4096, 8192])
@pytest.mark.parametrize("algorithm", ["TD", "UPGO", "VTRACE", "IMPACT"])
def test_log_depth_form_is_the_sequential_one(
        monkeypatch, algorithm, t, rows, gamma):
    x = _inputs(t * 31 + rows[0] * rows[1], rows[0], t, rows[1])
    out = _both_forms(monkeypatch, algorithm, x, 0.95, gamma)
    expect = _reference(algorithm, x, 0.95, gamma)
    for form, (targets, advantages) in out.items():
        assert np.all(np.isfinite(targets)), form
        np.testing.assert_allclose(targets, expect, err_msg=form, **TOL)
    for ours, theirs in zip(out["log_depth"], out["sequential"]):
        np.testing.assert_allclose(ours, theirs, **TOL)


@pytest.mark.parametrize("algorithm", ["TD", "UPGO", "VTRACE"])
def test_zero_times_minus_infinity_trap(monkeypatch, algorithm):
    """gamma 0.8 x lambda 0.95 underflows the running product ``a`` to
    0 within a few hundred moments.  An affine recursion stated as
    UPGO's triple with ``c = -inf`` would then read ``0 * -inf = nan``:
    TD and V-Trace carry pairs, only UPGO the third term, and every
    target stays finite and equal to the walked one."""
    x = _inputs(7, 2, 8192, 1)
    x["masks"] = np.ones_like(x["masks"])
    out = _both_forms(monkeypatch, algorithm, x, 0.95, 0.8)
    assert np.all(np.isfinite(out["log_depth"][0]))
    np.testing.assert_allclose(out["log_depth"][0], out["sequential"][0],
                               **TOL)
    import jax.numpy as jnp

    later = (jnp.zeros(()), jnp.ones(()), jnp.full((), -jnp.inf))
    earlier = (jnp.zeros(()), jnp.ones(()), jnp.full((), -jnp.inf))
    assert np.isnan(targets_module._compose(later, earlier)[2])
    assert len(targets_module._compose(later[:2], earlier[:2])) == 2


@pytest.mark.parametrize("algorithm", ["TD", "UPGO", "VTRACE"])
def test_grad_through_both_forms_agrees(monkeypatch, algorithm):
    import jax
    import jax.numpy as jnp

    x = _inputs(11, 2, 130, 2)

    def loss(values):
        targets, advantages = compute_target(
            algorithm, values, x["returns"], x["rewards"], 0.7, 0.9,
            x["rhos"], x["cs"], x["masks"])
        return jnp.sum(advantages ** 2) + jnp.sum(targets)

    grads = {}
    for form, constant in FORMS.items():
        monkeypatch.setattr(targets_module, "LOG_DEPTH_ABOVE", constant)
        grads[form] = np.asarray(jax.jit(jax.grad(loss))(
            jnp.asarray(x["values"])))
        assert np.all(np.isfinite(grads[form]))
    np.testing.assert_allclose(grads["log_depth"], grads["sequential"],
                               rtol=1e-4, atol=1e-5)


# -- the choice itself ----------------------------------------------------

def _parent_td(values, returns, rewards, lambda_, gamma):
    """``temporal_difference`` as it stood before the recursion had a
    second schedule (commit d9c9b14), to the letter."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rewards = jnp.zeros_like(values) if rewards is None else rewards

    def step(g_next, x):
        v_next, r, lam = x
        g = r + gamma * ((1.0 - lam) * v_next + lam * g_next)
        return g, g

    init = returns[:, -1]
    xs = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0),
                      (values[:, 1:], rewards[:, :-1], lambda_[:, 1:]))
    _, ys = lax.scan(step, init, xs, reverse=True)
    targets = jnp.concatenate([jnp.moveaxis(ys, 0, 1), init[:, None]],
                              axis=1)
    return targets, targets - values


def _lowered(fn, t, rows=(2, 1)):
    import jax
    import jax.numpy as jnp

    def named(values, returns, rewards, masks, rhos, cs):
        with jax.named_scope("loss.targets"):
            return fn(values, returns, rewards, masks, rhos, cs)

    shape = jax.ShapeDtypeStruct((rows[0], t, rows[1], 1), jnp.float32)
    return jax.jit(named).lower(*[shape] * 6).as_text()


@pytest.mark.parametrize("algorithm", ["TD", "UPGO", "VTRACE", "IMPACT"])
def test_the_length_of_the_time_axis_picks_the_form(monkeypatch, algorithm):
    """No key, preset or environment variable: at 8,192 moments the
    lowered text holds no ``while``; at a board game's 8 it is the
    text of the walked form alone, whatever the constant."""
    def target(values, returns, rewards, masks, rhos, cs):
        return compute_target(algorithm, values, returns, rewards, 0.95,
                              1.0, rhos, cs, masks)

    assert "stablehlo.while" not in _lowered(target, 8192)
    assert "stablehlo.while" not in _lowered(target, CONSTANT + 2)
    at_constant = _lowered(target, CONSTANT + 1)     # T - 1 == CONSTANT
    short = _lowered(target, 8)
    assert short.count("stablehlo.while") == 1
    assert at_constant.count("stablehlo.while") == 1
    monkeypatch.setattr(targets_module, "LOG_DEPTH_ABOVE", 10 ** 9)
    assert _lowered(target, 8) == short
    assert _lowered(target, CONSTANT + 1) == at_constant
    assert _lowered(target, 8192).count("stablehlo.while") == 1


def test_a_short_axis_lowers_to_the_text_it_always_had():
    """At a board game's 8 moments TD(lambda) lowers to the text of the
    function as it stood before the second schedule, byte for byte."""
    def ours(values, returns, rewards, masks, rhos, cs):
        return temporal_difference(values, returns, rewards, masks, 0.8)

    def parents(values, returns, rewards, masks, rhos, cs):
        return _parent_td(values, returns, rewards, masks, 0.8)

    for rows in ((256, 4), (128, 2)):
        assert _lowered(ours, 8, rows) == _lowered(parents, 8, rows)
