"""The delta-rule hybrid sequence policy at its tiny preset, seeded
weights, CPU, float32: the program against the plain reference
(``benchmarks/reference/olmo_hybrid_net.py``, ``olmo_hybrid_training.py``:
the recurrence a position at a time), the chunk-wise pass against the
one-token step walked through ``hidden``, the recurrence at its hard
ends (the negative-eigenvalue side, decays near one and strong ones),
the heads' shares against the uncut layers, what a dense net counts,
the step traced twice, and one epoch of ``main.py --train``'s path.
"""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import weights
from benchmarks.reference import olmo_hybrid_net, olmo_hybrid_training
from handyrl_tpu.environment import make_env
from handyrl_tpu.generation import Generator
from handyrl_tpu.models import sequence_net as sn
from handyrl_tpu.models.wrapper import TPUModel
from handyrl_tpu.ops import losses
from handyrl_tpu.ops.update import make_apply_fn

TINY = sn.PRESETS["tiny_hybrid"]
ENV_ARGS = {"env": "TokenTask", "net": "tiny_hybrid"}
TRAIN = {
    "turn_based_training": False, "observation": True, "gamma": 1.0,
    "forward_steps": 32, "burn_in_steps": 0, "compress_steps": 4,
    "entropy_regularization": 0.01, "entropy_regularization_decay": 0.1,
    "lambda": 0.95, "policy_target": "TD", "value_target": "TD",
    "compute_dtype": "float32", "batch_size": 4,
}
TRUNK = [f"layer_{i}" for i in range(len(TINY.layer_types))]
# the plain reference told the tiny preset's geometry (what the
# weights' shapes do not say)
TINY_GEOMETRY = {
    "layer_types": TINY.layer_types, "attention_head_dim": TINY.head_dim,
    "query_block": 16, "scan_block": 8}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture()
def tiny_geometry(monkeypatch):
    for key, value in TINY_GEOMETRY.items():
        monkeypatch.setitem(olmo_hybrid_net.GEOMETRY, key, value)


@pytest.fixture(scope="module")
def model():
    net = TPUModel(sn.sequence_net("tiny_hybrid"))
    shapes = weights.param_shapes(net.module, np.int32(0),
                                  net.init_hidden([1]))
    net.params = weights.make_params(shapes, 7, (), (), TRUNK)
    return net


@pytest.fixture(scope="module")
def episodes(model):
    random.seed(3)
    env = make_env(ENV_ARGS)
    play = Generator(env, {"observation": True, "gamma": 1.0,
                           "compress_steps": 4, "episode_compress": False})
    job = {"player": [0], "model_id": {0: 0}}
    return [play.generate({0: model}, job) for _ in range(6)]


def _tokens(seed=1, batch=2, length=TINY.sequence_length):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length),
                              0, TINY.vocab)


def _logits(policy):
    return policy.features @ policy.kernel


# -- the net against the plain reference ----------------------------------

def test_the_preset_declares_what_the_module_chooses_by():
    """Nothing reads a preset's name: the kinds of layer, the delta
    heads' widths, the share of the heads, which side of a branch is
    normed, whether attention has a gate and what its q/k norm spans."""
    big = sn.PRESETS["olmo_hybrid_tp2"]
    for z in (TINY, big):
        assert set(z.layer_types) == {sn.LINEAR, sn.FULL}
        assert z.dense_layers == len(z.layer_types) and not z.experts
        assert z.post_norms and not z.pre_norms and not z.embed_scale
        assert z.qk_norm_whole and not z.attention_gate
        assert 2 * z.heads_held == z.heads == z.kv_heads
        assert not z.nextn_modules and not z.latent_kv and not z.window
    assert big.layer_types == (sn.LINEAR,) * 3 + (sn.FULL,)
    assert (big.hidden, big.head_dim, big.delta_key_dim, big.delta_value_dim,
            big.conv_taps, big.dense_width, big.heads_held, big.vocab,
            big.delta_chunk, big.eps) == (
                3840, 128, 96, 192, 4, 11008, 15, 12544, 64, 1e-6)
    for name in ("tiny", "trinity_mini_ep8", "tiny_latent",
                 "joyai_flash_ep16"):
        z = sn.PRESETS[name]
        assert sn.LINEAR not in z.layer_types and z.pre_norms
        assert sn.held_heads(z) == (z.heads, z.kv_heads)
        assert not z.qk_norm_whole and z.attention_gate


def test_logits_and_value_equal_the_plain_reference(model, tiny_geometry):
    tokens = _tokens()
    out = model.module.apply({"params": model.params}, tokens, None)
    ref = olmo_hybrid_net.forward(model.params, tokens)
    np.testing.assert_allclose(_logits(out["policy"]), ref["policy"],
                               atol=3e-6)
    np.testing.assert_allclose(out["value"], ref["value"], atol=3e-6)
    assert float(np.abs(ref["policy"]).max()) > 0.1


def _batch(episodes):
    columns = [olmo_hybrid_training.episode_columns(ep) for ep in episodes]
    return olmo_hybrid_training.gather(
        columns, [0, 1, 2, 3], [0] * 4, [0] * 4, 32, 0, True)


def _program_loss(model, batch):
    cfg = losses.LossConfig.from_config(TRAIN)
    apply_fn = make_apply_fn(model, "float32")

    def program(params):
        device = dict(jax.tree.map(jnp.asarray, batch),
                      action_mask=jnp.zeros((4, 32, 1, 0)))
        out, _ = losses.compute_loss(apply_fn, params, device,
                                     losses.SEQUENCE, cfg)
        return out["total"], out

    return program


def test_loss_and_every_gradient_leaf_equal_the_reference(
        model, episodes, tiny_geometry):
    """What holds the chunk-wise pass's derivative to the per-position
    definition: every leaf, the decay's and the convolutions' too."""
    batch = _batch(episodes)

    def reference(params):
        return sum(olmo_hybrid_training.loss(
            olmo_hybrid_net, params,
            jax.tree.map(lambda a: a[b:b + 1], batch), TRAIN)[0]
            for b in range(4))

    (total, _), grads = jax.jit(jax.value_and_grad(
        _program_loss(model, batch), has_aux=True))(model.params)
    ref_total, ref_grads = jax.jit(jax.value_and_grad(reference))(
        model.params)
    np.testing.assert_allclose(total, ref_total, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    names = {jax.tree_util.keystr(path) for path, _ in flat}
    assert {"['layer_0']['delta']['A_log']", "['layer_0']['delta']['dt_bias']",
            "['layer_2']['delta']['k_conv']['kernel']",
            "['layer_1']['attn']['q_norm']['scale']"} <= names
    for (path, ours), theirs in zip(flat, jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(theirs).max()) > 0, path
        np.testing.assert_allclose(
            ours, theirs, rtol=2e-4, atol=2e-5 * float(jnp.abs(theirs).max()),
            err_msg=jax.tree_util.keystr(path))


def test_a_dense_net_counts_its_fill_and_its_retention_and_no_expert(
        model, episodes):
    """No expert layer: the step's counters are the window's fill and
    the delta layers' own."""
    batch = _batch(episodes)
    _, parts = _program_loss(model, batch)(model.params)
    assert not {"expert_load_max", "expert_load_mean",
                "held_pick_share"} & set(parts)
    assert float(parts["window_fill"]) == pytest.approx(
        batch["episode_mask"].mean())
    assert set(parts) & set(losses.SEQUENCE_COUNTERS) == {
        "window_fill", "delta_retention"}
    # exp(g) by hand, over real positions alone, held heads, delta layers
    tokens = jnp.where(batch["episode_mask"][:, :, 0, 0] > 0,
                       batch["observation"][:, :, 0], 0)
    real = batch["episode_mask"][:, :, 0, 0] > 0
    h = model.params["embedding"][tokens]
    p = model.params["layer_0"]["delta"]
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        h @ p["a"]["kernel"] + p["dt_bias"])
    first = float(jnp.exp(g)[real].mean())
    out = model.module.apply(
        {"params": model.params}, jnp.where(real, tokens, -1), None)
    assert 0.2 < float(out["counts"]["delta_retention"]) < 0.8
    one = sn.SequencePolicyNet(TINY._replace(
        layer_types=(sn.LINEAR,), dense_layers=1))
    alone = one.apply({"params": {
        k: v for k, v in model.params.items()
        if not k.startswith("layer_") or k == "layer_0"}},
        jnp.where(real, tokens, -1), None)
    assert float(alone["counts"]["delta_retention"]) == pytest.approx(
        first, rel=1e-5)
    assert float(parts["delta_retention"]) == pytest.approx(
        float(out["counts"]["delta_retention"]), rel=1e-6)


# -- the chunk-wise pass against the one-token step ---------------------------

def test_the_chunkwise_pass_equals_the_step_walked_through_hidden(model):
    """27 positions in chunks of 8: three whole chunks and a part of
    one; the actor's ``hidden`` holds a matrix state and the last
    convolution inputs for the delta layers BESIDE keys and values for
    the attention layer."""
    length = 27
    assert length % TINY.delta_chunk
    tokens = _tokens(seed=5, length=length)
    out = model.module.apply({"params": model.params}, tokens, None)
    hidden = model.init_hidden([2])
    heads, dk, dv = TINY.heads_held, TINY.delta_key_dim, TINY.delta_value_dim
    assert {k: v.shape[1:] for k, v in hidden.items() if k != "pos"} == {
        "state": (2, heads, dv, dk),
        "conv": (2, TINY.conv_taps - 1, heads * (2 * dk + dv)),
        "k": (1, TINY.sequence_length, heads, TINY.head_dim),
        "v": (1, TINY.sequence_length, heads, TINY.head_dim)}
    stepped = []
    for t in range(length):
        o = model.module.apply({"params": model.params}, tokens[:, t], hidden)
        hidden = o["hidden"]
        stepped.append(o["policy"])
        np.testing.assert_allclose(o["value"], out["value"][:, t], atol=5e-6)
    np.testing.assert_allclose(jnp.stack(stepped, 1), _logits(out["policy"]),
                               atol=5e-6)
    assert int(hidden["pos"][0]) == length
    assert float(jnp.abs(hidden["state"]).max()) > 0
    big = sn.sequence_net("olmo_hybrid_tp2")
    shapes = jax.eval_shape(lambda: big.init_hidden((1,)))
    assert {k: s.shape[1:] for k, s in shapes.items() if k != "pos"} == {
        "state": (3, 15, 192, 96), "conv": (3, 3, 5760),
        "k": (1, 4096, 15, 128), "v": (1, 4096, 15, 128)}


def test_padding_past_an_episodes_end_changes_no_real_position(model):
    """An episode shorter than the window: what follows its last
    position (token -1, computed like any other) reaches none of its
    own, in the heads and in every gradient."""
    tokens = _tokens(seed=6)
    real = 19
    padded = tokens.at[:, real:].set(-1)
    other = tokens.at[:, real:].set(7)

    def heads(params, window):
        out = model.module.apply({"params": params}, window, None)
        return (_logits(out["policy"])[:, :real], out["value"][:, :real],
                out["counts"]["delta_retention"])

    def scalar(params, window):
        logits, value, _ = heads(params, window)
        return jnp.square(logits).sum() + value.sum()

    want = heads(model.params, tokens[:, :real])
    for window in (padded, other):
        got = heads(model.params, window)
        np.testing.assert_allclose(got[0], want[0], atol=3e-6)
        np.testing.assert_allclose(got[1], want[1], atol=3e-6)
    # the counter is over real positions alone
    assert float(heads(model.params, padded)[2]) == pytest.approx(
        float(want[2]), rel=1e-5)
    # what the padding holds moves no gradient by one bit; against the
    # window cut at the episode's end (other shapes, another order of
    # the same sums) the gradients agree to rounding
    got_back = jax.grad(scalar)(model.params, padded)
    other_back = jax.grad(scalar)(model.params, other)
    want_back = jax.grad(scalar)(model.params, tokens[:, :real])
    for g, o, w in zip(*map(jax.tree.leaves,
                            (got_back, other_back, want_back))):
        np.testing.assert_array_equal(g, o)
        np.testing.assert_allclose(
            g, w, atol=3e-4 * float(jnp.abs(w).max()))


# -- the recurrence at its hard ends ------------------------------------------

@pytest.mark.parametrize("beta_range,decay", [
    ((1.0, 2.0), -0.5), ((0.0, 2.0), -0.001), ((0.0, 2.0), -3.0)],
    ids=["negative_eigenvalues", "decay_near_one", "strong_decay"])
def test_the_chunkwise_recurrence_equals_the_rule_a_position_at_a_time(
        beta_range, decay):
    """``delta_scan`` against the plain reference's ``recurrence``, the
    rule as it is written, output and every operand's gradient: with
    every ``beta`` in (1, 2), where ``I - beta k k^T`` turns a state
    over along ``k``; with decays near one; and with ``g`` about -3 a
    position over chunks of 64, where a chunk's cumulative log-decay
    reaches -190 and ``exp`` of its negative has no float32: every
    decay is ``exp(G_r - G_i)`` for ``i <= r`` alone."""
    B, T, H, dk, dv, chunk = 2, 150, 2, 8, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    q = jax.random.normal(keys[0], (B, T, H, dk)) / np.sqrt(dk)
    k = jax.random.normal(keys[1], (B, T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, T, H, dv))
    beta = jax.random.uniform(keys[3], (B, T, H), minval=beta_range[0],
                              maxval=beta_range[1])
    g = decay * jax.random.uniform(keys[4], (B, T, H), minval=0.8,
                                   maxval=1.2)
    weight = jax.random.normal(keys[5], (B, T, H, dv))
    if decay == -3.0:
        assert float(jnp.cumsum(g[:, :chunk], 1).min()) < -170

    def program(*operands):
        return sn.delta_scan(*operands, chunk)

    def reference(*operands):
        return jnp.stack([olmo_hybrid_net.recurrence(
            *(x[b] for x in operands), 8) for b in range(B)])

    def both(fn):
        return fn(q, k, v, g, beta), jax.grad(
            lambda *operands: (fn(*operands) * weight).sum(),
            argnums=range(5))(q, k, v, g, beta)

    (got, got_back), (want, want_back) = both(program), both(reference)
    assert np.isfinite(got).all() and float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)
    for name, a, b in zip("qkvgb", got_back, want_back):
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5 * max(
            1.0, float(jnp.abs(b).max())), err_msg=name)


def test_a_chunks_system_is_inverted_exactly_where_keys_repeat():
    """``unit_lower_inverse`` at the hard end of a chunk's system: 64
    positions whose keys all but repeat, written at ``beta`` near 2 and
    never forgotten, so ``L`` is large (its powers reach 1e26 and a
    series in them would cancel to nothing) while ``(I + L)^-1`` stays
    of order one.  Row by row it agrees with float64, and so does its
    closed-form derivative."""
    C, dk = 64, 8
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    k = jnp.ones((C, dk)) + 0.05 * jax.random.normal(keys[0], (C, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.random.uniform(keys[1], (C,), minval=1.8, maxval=2.0)
    lower = jnp.tril(beta[:, None] * (k @ k.T), -1)[None]
    want = np.linalg.inv(np.eye(C) + np.asarray(lower[0], np.float64))
    assert np.abs(np.linalg.matrix_power(
        np.asarray(lower[0], np.float64), 32)).max() > 1e25
    assert np.abs(want).max() < 4
    np.testing.assert_allclose(sn.unit_lower_inverse(lower)[0], want,
                               atol=2e-5)
    weight = jax.random.normal(keys[2], (1, C, C))
    back = jax.grad(lambda x: (sn.unit_lower_inverse(x) * weight).sum())(
        lower)
    # d(A^-1) = -A^-1 dA A^-1, in float64, below the diagonal
    plain = -np.tril(want.T @ np.asarray(weight[0], np.float64) @ want.T, -1)
    np.testing.assert_allclose(back[0], plain, atol=2e-5 * np.abs(plain).max())
    assert float(jnp.abs(jnp.triu(back)).max()) == 0.0


def test_the_one_token_rule_is_the_rule_as_written():
    """``delta_step`` against ``S (I - beta k k^T)`` written out."""
    keys = jax.random.split(jax.random.PRNGKey(10), 6)
    S = jax.random.normal(keys[0], (3, 2, 16, 8))
    q, k = (jax.random.normal(key, (3, 2, 8)) for key in keys[1:3])
    v = jax.random.normal(keys[3], (3, 2, 16))
    g = -jax.random.uniform(keys[4], (3, 2))
    beta = 2 * jax.random.uniform(keys[5], (3, 2))
    state, o = sn.delta_step(S, q, k, v, g, beta)
    eye = jnp.eye(8)
    for n in range(3):
        for h in range(2):
            turned = eye - beta[n, h] * jnp.outer(k[n, h], k[n, h])
            want = jnp.exp(g[n, h]) * S[n, h] @ turned \
                + beta[n, h] * jnp.outer(v[n, h], k[n, h])
            np.testing.assert_allclose(state[n, h], want, atol=1e-5)
            np.testing.assert_allclose(o[n, h], want @ q[n, h], atol=1e-5)


# -- the share ---------------------------------------------------------------

UNCUT = TINY._replace(heads_held=0)


def _heads_of(kernel, first, count, width, axis=-1):
    return jax.lax.slice_in_dim(
        kernel, first * width, (first + count) * width, axis=axis)


def test_the_delta_mixers_shares_add_up_to_the_uncut_layer():
    """Four heads, shares of two and two: a head's state, convolution,
    gates and norm are its own, and ``Wo`` is a sum over heads, so the
    two partial results sum to the uncut mixer's exactly."""
    z = TINY
    dk, dv = z.delta_key_dim, z.delta_value_dim
    whole = sn.DeltaMixer(UNCUT)
    a = jax.random.normal(jax.random.PRNGKey(0), (2, 27, z.hidden))
    shapes = jax.eval_shape(
        lambda: whole.init(jax.random.PRNGKey(0), a))["params"]
    params = weights.make_params(shapes, 11, (), (), ["q", "k", "v", "a",
                                                      "b", "g", "o"])
    uncut, _, kept = whole.apply({"params": params}, a)
    assert shapes["o"]["kernel"].shape == (z.heads * dv, z.hidden)
    total, retained = 0.0, []
    for first in range(0, z.heads, z.heads_held):
        cut = lambda kernel, width, axis=-1: _heads_of(  # noqa: E731
            kernel, first, z.heads_held, width, axis)
        held = {
            "A_log": cut(params["A_log"], 1),
            "dt_bias": cut(params["dt_bias"], 1),
            "o_norm": params["o_norm"],
            "o": {"kernel": cut(params["o"]["kernel"], dv, 0)},
            **{name: {"kernel": cut(params[name]["kernel"], width)}
               for name, width in (("q", dk), ("k", dk), ("v", dv),
                                   ("a", 1), ("b", 1), ("g", dv),
                                   ("q_conv", dk), ("k_conv", dk),
                                   ("v_conv", dv))}}
        y, _, r = sn.DeltaMixer(z).apply({"params": held}, a)
        assert jax.tree.map(lambda x: x.shape, held) == jax.tree.map(
            lambda x: x.shape, jax.eval_shape(lambda: sn.DeltaMixer(
                z).init(jax.random.PRNGKey(0), a))["params"])
        total = total + y
        retained.append(float(r))
    np.testing.assert_allclose(total, uncut, atol=2e-6)
    assert float(jnp.abs(uncut).max()) > 0.1
    assert np.mean(retained) == pytest.approx(float(kept), rel=1e-5)


def test_the_attentions_shares_add_up_once_handed_the_whole_mean_square(
        monkeypatch):
    """The mean square under the q/k norm is the ONLY thing a share of
    the heads changes in the attention: each share handed the whole
    projection's statistic in place of its own, the two partial results
    sum to the uncut layer's; left with its own they do not."""
    z = TINY
    D = z.head_dim
    whole = sn.Attention(UNCUT, sn.FULL)
    a = jax.random.normal(jax.random.PRNGKey(1), (2, 27, z.hidden))
    shapes = jax.eval_shape(
        lambda: whole.init(jax.random.PRNGKey(0), a))["params"]
    params = weights.make_params(shapes, 12, (), (), ["q", "k", "v", "o"])
    assert set(params) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    uncut, _ = whole.apply({"params": params}, a)
    squares = [jnp.mean(jnp.square(a @ params[name]["kernel"]), -1,
                        keepdims=True) for name in "qk"]
    plain = sn.rms_norm

    def shares(norm):
        monkeypatch.setattr(sn, "rms_norm", norm)
        total = 0.0
        for first in range(0, z.heads, z.heads_held):
            held = {name: {"kernel": _heads_of(
                params[name]["kernel"], first, z.heads_held, D)}
                for name in "qkv"}
            held["o"] = {"kernel": _heads_of(
                params["o"]["kernel"], first, z.heads_held, D, 0)}
            for name in ("q_norm", "k_norm"):
                held[name] = {"scale": _heads_of(
                    params[name]["scale"], first, z.heads_held, D)}
            y, _ = sn.Attention(z, sn.FULL).apply({"params": held}, a)
            total = total + y
        monkeypatch.setattr(sn, "rms_norm", plain)
        return total

    handed = iter(squares * 2)      # q's, then k's, a share

    def whole_statistic(x, scale, eps):
        return x * jax.lax.rsqrt(next(handed) + eps) * scale

    np.testing.assert_allclose(shares(whole_statistic), uncut, atol=2e-6)
    assert next(handed, None) is None
    assert float(jnp.abs(shares(plain) - uncut).max()) > 1e-3


def test_the_parameters_keep_their_published_names_and_shapes(model):
    """A checkpoint's reader and the benchmark's reference fit: a delta
    layer's seven projections, three filters, two one-a-head vectors
    and its gated norm's gain; an attention's four projections and the
    two gains over its whole (held) projection; no norm going in."""
    delta = {
        "A_log": (2,), "dt_bias": (2,), "a": {"kernel": (64, 2)},
        "b": {"kernel": (64, 2)}, "g": {"kernel": (64, 32)},
        "q": {"kernel": (64, 16)}, "k": {"kernel": (64, 16)},
        "v": {"kernel": (64, 32)}, "o": {"kernel": (32, 64)},
        "q_conv": {"kernel": (4, 16)}, "k_conv": {"kernel": (4, 16)},
        "v_conv": {"kernel": (4, 32)}, "o_norm": {"scale": (16,)}}
    attn = {"q": {"kernel": (64, 32)}, "k": {"kernel": (64, 32)},
            "v": {"kernel": (64, 32)}, "o": {"kernel": (32, 64)},
            "q_norm": {"scale": (32,)}, "k_norm": {"scale": (32,)}}
    rest = {"mlp": {"w1": {"kernel": (64, 128)}, "w3": {"kernel": (64, 128)},
                    "w2": {"kernel": (128, 64)}},
            "post_attn_norm": {"scale": (64,)},
            "post_mlp_norm": {"scale": (64,)}}
    shapes = jax.tree.map(lambda a: a.shape, model.params)
    assert shapes["layer_0"] == shapes["layer_2"] == dict(rest, delta=delta)
    assert shapes["layer_1"] == dict(rest, attn=attn)
    assert set(shapes) == set(TRUNK) | {
        "embedding", "head", "value_head", "final_norm"}


def test_the_modules_own_initialiser_keeps_most_of_a_state():
    """A net initialised by the module (``main.py --train`` from no
    checkpoint): a decay rate uniform in (0, 16), a step log-uniform in
    (0.001, 0.1), so a position keeps between a fifth and all but a
    thousandth of its state."""
    mixer = sn.DeltaMixer(TINY._replace(heads_held=0, heads=64))
    a = jnp.zeros((1, 4, TINY.hidden))
    params = mixer.init(jax.random.PRNGKey(5), a)["params"]
    rate, step = jnp.exp(params["A_log"]), jax.nn.softplus(params["dt_bias"])
    assert 0 < float(rate.min()) and float(rate.max()) < 16
    assert 0.001 <= float(step.min()) and float(step.max()) <= 0.1001
    kept = jnp.exp(-rate * step)
    assert 0.2 < float(kept.min()) and float(kept.max()) < 1
    assert mixer.apply({"params": params}, a)[2] == pytest.approx(
        float(kept.mean()), rel=1e-5)


def test_the_step_of_the_new_preset_traces_twice(model, episodes):
    """Whatever the first trace of the step made must serve the second
    (the cost harvest traces the step before the program compiles
    it)."""
    batch = jax.tree.map(jnp.asarray, _batch(episodes))
    batch["action_mask"] = jnp.zeros((4, 32, 1, 0))
    program = _program_loss(model, batch)
    texts = [jax.jit(jax.grad(lambda p: program(p)[0])).lower(
        model.params).as_text() for _ in range(2)]
    assert texts[0] == texts[1]
    assert "tpu_custom_call" not in texts[0]        # heads of 16: XLA's path


# -- the normal path ---------------------------------------------------------

def test_one_epoch_of_the_training_path_with_the_hybrid_preset(
        tmp_path, monkeypatch):
    """``main.py --train``'s path (``Learner(args).run()``): two actor
    processes play the token task through the one-token step, a state
    and a cache side by side in ``hidden``; the learner trains whole
    sequences through the ring and the fused replay step."""
    from handyrl_tpu.learner import Learner

    monkeypatch.chdir(tmp_path)
    args = {
        "env_args": dict(ENV_ARGS),
        "train_args": dict(
            TRAIN, update_episodes=12, minimum_episodes=8,
            maximum_episodes=64, epochs=1, num_batchers=1, eval_rate=0.0,
            worker={"num_parallel": 2}, seed=2, batch_size=1,
            metrics_path="metrics.jsonl"),
        "worker_args": {"num_parallel": 2, "server_address": ""},
    }
    learner = Learner(args)
    assert learner.trainer._replay_step is not None
    learner.run()
    assert learner.trainer.failure is None
    assert learner.model_epoch == 1 and learner.trainer.steps > 0
    assert os.path.exists(tmp_path / "models" / "1.ckpt")
