"""The latent-attention sequence policy with its next-next-token module
at its tiny preset, seeded weights, CPU, float32: the program against
the plain reference (``benchmarks/reference/joyai_net.py``,
``joyai_training.py``), the actor's steps through the latent cache
against the learner's pass, the share against the uncut layer, the
rotation on columns put half-split against the parent's layer, the module's positions, the fused kernel at the
latent heads' widths, the step traced twice, and one epoch of
``main.py --train``'s path.
"""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import weights
from benchmarks.reference import joyai_net, joyai_training
from handyrl_tpu.environment import make_env
from handyrl_tpu.generation import Generator
from handyrl_tpu.models import sequence_net as sn
from handyrl_tpu.models.wrapper import TPUModel
from handyrl_tpu.ops import losses
from handyrl_tpu.ops.update import make_apply_fn

TINY = sn.PRESETS["tiny_latent"]
ENV_ARGS = {"env": "TokenTask", "net": "tiny_latent"}
TRAIN = {
    "turn_based_training": False, "observation": True, "gamma": 1.0,
    "forward_steps": 32, "burn_in_steps": 0, "compress_steps": 4,
    "entropy_regularization": 0.01, "entropy_regularization_decay": 0.1,
    "lambda": 0.95, "policy_target": "TD", "value_target": "TD",
    "compute_dtype": "float32", "batch_size": 4,
}
TRUNK = [f"layer_{i}" for i in range(len(TINY.layer_types))] + ["mtp"]
# the plain reference told the tiny preset's geometry (what the
# weights' shapes do not say)
TINY_GEOMETRY = {
    "num_hidden_layers": len(TINY.layer_types), "first_k_dense_replace": 1,
    "num_attention_heads": TINY.heads, "query_block": 16,
    "num_experts_per_tok": TINY.experts_per_token}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture()
def tiny_geometry(monkeypatch):
    for key, value in TINY_GEOMETRY.items():
        monkeypatch.setitem(joyai_net.GEOMETRY, key, value)


@pytest.fixture(scope="module")
def model():
    net = TPUModel(sn.sequence_net("tiny_latent"))
    shapes = weights.param_shapes(net.module, np.int32(0),
                                  net.init_hidden([1]))
    net.params = weights.make_params(shapes, 7, (), ["experts"], TRUNK)
    return net


@pytest.fixture(scope="module")
def episodes(model):
    random.seed(3)
    env = make_env(ENV_ARGS)
    play = Generator(env, {"observation": True, "gamma": 1.0,
                           "compress_steps": 4, "episode_compress": False})
    job = {"player": [0], "model_id": {0: 0}}
    return [play.generate({0: model}, job) for _ in range(6)]


def _tokens(seed=1, batch=2):
    return jax.random.randint(jax.random.PRNGKey(seed),
                              (batch, TINY.sequence_length), 0, TINY.vocab)


def _logits(policy):
    return policy.features @ policy.kernel


# -- the net against the plain reference ----------------------------------

def test_the_preset_declares_what_the_module_chooses_by():
    """Nothing reads a preset's name: a latent rank, the heads' parts,
    whether a branch is normed coming out, whether a module follows."""
    big = sn.PRESETS["joyai_flash_ep16"]
    for z in (TINY, big):
        assert set(z.layer_types) == {sn.LATENT} and z.latent_kv
        assert z.rope_interleave and z.nextn_modules == 1
        assert not z.post_norms and not z.embed_scale
        assert z.head_dim + z.rope_dim != z.value_dim
    assert (big.latent_q, big.latent_kv, big.head_dim, big.rope_dim,
            big.value_dim) == (1536, 512, 128, 64, 128)
    for name in ("tiny", "trinity_mini_ep8"):
        z = sn.PRESETS[name]
        assert not z.latent_kv and not z.nextn_modules and z.post_norms


def test_logits_value_and_the_modules_logits_equal_the_plain_reference(
        model, tiny_geometry):
    tokens = _tokens()
    out = model.module.apply({"params": model.params}, tokens, None)
    ref = joyai_net.forward(model.params, tokens)
    np.testing.assert_allclose(_logits(out["policy"]), ref["policy"],
                               atol=3e-6)
    np.testing.assert_allclose(out["value"], ref["value"], atol=3e-6)
    # the module's last position has no token after it: it reaches no
    # term, and the two sides give it different ones
    np.testing.assert_allclose(_logits(out["mtp"])[:, :-1],
                               ref["mtp"][:, :-1], atol=3e-6)
    # four layers hold experts: three of the trunk's and the module's
    assert out["counts"]["expert_load"].shape == (
        len(TINY.layer_types) - TINY.dense_layers + 1, TINY.experts_held)


def _batch(episodes):
    columns = [joyai_training.episode_columns(ep) for ep in episodes]
    return joyai_training.gather(
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


def test_loss_with_the_term_and_every_gradient_leaf_equal_the_reference(
        model, episodes, tiny_geometry, monkeypatch):
    batch = _batch(episodes)

    def reference(params):
        rows = [joyai_training.loss(
            joyai_net, params, jax.tree.map(lambda a: a[b:b + 1], batch),
            TRAIN) for b in range(4)]
        return sum(r[0] for r in rows), sum(r[1]["mtp"] for r in rows)

    (total, parts), grads = jax.jit(jax.value_and_grad(
        _program_loss(model, batch), has_aux=True))(model.params)
    (ref_total, ref_term), ref_grads = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(model.params)
    np.testing.assert_allclose(total, ref_total, rtol=1e-5)
    np.testing.assert_allclose(parts["mtp_loss"], ref_term, rtol=1e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    names = {jax.tree_util.keystr(path) for path, _ in flat}
    assert "['embedding']" in names and "['head']['kernel']" in names
    for (path, ours), theirs in zip(flat, jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(theirs).max()) > 0, path
        np.testing.assert_allclose(
            ours, theirs, rtol=2e-4, atol=2e-5 * float(jnp.abs(theirs).max()),
            err_msg=jax.tree_util.keystr(path))
    # the term is in the total, at its weight
    monkeypatch.setattr(losses, "NEXTN_WEIGHT", 0.0)
    bare, _ = _program_loss(model, batch)(model.params)
    assert float(total - bare) == pytest.approx(
        0.1 * float(ref_term), rel=1e-4)


def test_the_modules_gradient_reaches_trunk_embedding_and_head(
        model, episodes):
    """Embedding and head are each fed from two places: the module's
    term alone moves them, and the trunk below it."""
    batch = jax.tree.map(jnp.asarray, _batch(episodes))
    apply_fn = make_apply_fn(model, "float32")

    def term(params):
        out = losses.forward_prediction(
            apply_fn, params, losses.SEQUENCE,
            dict(batch, action_mask=jnp.zeros((4, 32, 1, 0))),
            losses.LossConfig.from_config(TRAIN))
        return losses.nextn_term(out["mtp"], out["mtp_target"])[0]

    grads = jax.grad(term)(model.params)
    for leaf in (grads["embedding"], grads["head"]["kernel"],
                 grads["layer_0"]["attn"]["q_a"]["kernel"],
                 grads["mtp"]["join"]["kernel"],
                 grads["mtp"]["layer"]["moe"]["experts"]["w2"]["kernel"]):
        assert float(jnp.abs(leaf).max()) > 0
    # the value head and the trunk's last norm are no part of it
    assert float(jnp.abs(grads["value_head"]["kernel"]).max()) == 0
    assert float(jnp.abs(grads["final_norm"]["scale"]).max()) == 0


def test_the_last_two_rows_of_an_episode_and_all_padding_take_no_term(
        model, episodes):
    batch = _batch(episodes)
    lengths = batch["episode_mask"][:, :, 0, 0].sum(1).astype(int)
    assert (lengths < 32).any() and (lengths > 2).all()
    by_hand = np.maximum(lengths - 2, 0).sum() / (4 * 32)
    (_, parts), _ = jax.value_and_grad(
        _program_loss(model, batch), has_aux=True)(model.params)
    assert float(parts["mtp_target_share"]) == pytest.approx(by_hand)
    # the targets themselves: the window's own token two rows on
    tokens = jnp.where(batch["episode_mask"][:, :, 0, 0] > 0,
                       batch["observation"][:, :, 0], -1)
    apply_fn = make_apply_fn(model, "float32")
    out = losses.forward_prediction(
        apply_fn, model.params, losses.SEQUENCE,
        dict(jax.tree.map(jnp.asarray, batch),
             action_mask=jnp.zeros((4, 32, 1, 0))),
        losses.LossConfig.from_config(TRAIN))
    for b, n in enumerate(lengths):
        np.testing.assert_array_equal(
            out["mtp_target"][b, :n - 2], tokens[b, 2:n])
        assert (out["mtp_target"][b, n - 2:] == -1).all()
    # a changed token past the last target moves no term
    moved = dict(batch, observation=batch["observation"].copy())
    moved["observation"][0, lengths[0]:] = 5
    (_, again), _ = jax.value_and_grad(
        _program_loss(model, moved), has_aux=True)(model.params)
    assert float(again["mtp_loss"]) == float(parts["mtp_loss"])


# -- the share ---------------------------------------------------------------

# an expert layer of this preset's form at lane-wide widths, which the
# grouped path takes: 2 x 128 positions x 2 picks are one tile of rows
LANE_WIDE = TINY._replace(hidden=128, expert_width=128)


@pytest.mark.parametrize("body", ["dense", "grouped"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(
        body, tiny_geometry, request):
    """Every chip's held experts' part of one expert layer of this
    preset, the shared expert counted once, is what the uncut reference
    gives for the whole layer: by the dense stack at the tiny preset's
    widths, and by the grouped products' bodies at lane-wide ones."""
    z, positions = TINY, 16
    if body == "grouped":
        request.getfixturevalue("chips_path")
        z, positions = LANE_WIDE, 128
        assert sn._grouped_tiles(
            2 * positions * z.experts_per_token, z.hidden, z.expert_width)
    shares = z.experts // z.experts_held
    whole = sn.SparseExperts(z._replace(experts_held=z.experts))
    m = jax.random.normal(jax.random.PRNGKey(0), (2, positions, z.hidden))
    shapes = jax.eval_shape(
        lambda: whole.init(jax.random.PRNGKey(0), m))["params"]
    params = weights.make_params(shapes, 11, (), ["experts"], ["router"])
    flat = m.reshape(-1, z.hidden)
    uncut = joyai_net.experts(flat, params, None, joyai_net.GEOMETRY)
    shared = joyai_net.swiglu(
        flat, *(params["shared"][k]["kernel"] for k in ("w1", "w3", "w2")),
        None)
    total, picks = 0.0, 0
    for share in range(shares):
        first = share * z.experts_held
        held = dict(params, experts=jax.tree.map(
            lambda k: k[first:first + z.experts_held],
            params["experts"]))
        y, load = sn.SparseExperts(
            z._replace(first_expert=first)).apply({"params": held}, m)
        total = total + y.reshape(-1, z.hidden) - shared
        picks += int(load.sum())
    np.testing.assert_allclose(total + shared, uncut, atol=5e-6)
    assert picks == 2 * positions * z.experts_per_token
    assert joyai_net.GEOMETRY["route_scale"] == z.route_scale == 2.5


def test_an_expert_three_quarters_of_a_tile_wide_takes_its_own_tiles(
        monkeypatch):
    """This net's experts are 768 wide against a residual of 2,048: a
    product's contracted and output widths take each its own tile (768
    whole, 2,048 in two), going forward and coming back.  Held here at
    an eighth of those widths in tiles of 128: 384 in three, 256 in
    two, the kernels' bodies as plain JAX against the dense stack."""
    z = sn.PRESETS["joyai_flash_ep16"]
    assert sn._grouped_tiles(
        z.sequence_length * z.experts_per_token, z.hidden,
        z.expert_width) == {2048: 1024, 768: 768}
    monkeypatch.setattr(sn, "GROUPED_WIDTH", 128)
    N, k, held, d, f = 128, 4, 4, 256, 384
    assert sn._grouped_tiles(N * k, d, f) == {d: 128, f: 128}
    keys = jax.random.split(jax.random.PRNGKey(6), 7)
    m = jax.random.normal(keys[0], (N, d))
    kernels = tuple(
        jax.random.normal(key, shape) / np.sqrt(shape[1])
        for key, shape in zip(keys[1:4], [
            (held, d, f), (held, d, f), (held, f, d)]))
    selected = jax.lax.top_k(jax.random.uniform(keys[4], (N, 16)), k)[1]
    router = jax.random.uniform(keys[5], (N, k), minval=0.1)
    weight = jax.random.normal(keys[6], m.shape)
    here = selected[..., None] == jnp.arange(held)
    assert 0 < int(here.sum()) < N * k

    def both(experts):
        def scalar(m, router, *kernels):
            return (experts(m, here, router, *kernels) * weight).sum()
        return (experts(m, here, router, *kernels),
                jax.grad(scalar, argnums=range(5))(m, router, *kernels))

    (got, got_back), (want, want_back) = both(
        lambda *operands: sn.grouped_experts(*operands, interpret=True)), \
        both(sn.dense_experts)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, w in zip(got_back, want_back):       # the router's reach ~10
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# -- the rotation ------------------------------------------------------------

def _pairwise(x, positions, theta):
    """The published rotation, pair ``(2i, 2i + 1)`` by pair, in numpy."""
    d = x.shape[-1]
    want = np.zeros_like(x)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            for i in range(d // 2):
                angle = positions[b, t] * theta ** (-2 * i / d)
                c, s = np.cos(angle), np.sin(angle)
                even, odd = x[b, t, :, 2 * i], x[b, t, :, 2 * i + 1]
                want[b, t, :, 2 * i] = even * c - odd * s
                want[b, t, :, 2 * i + 1] = odd * c + even * s
    return want


def test_columns_put_half_split_and_rotated_so_equal_the_pairwise_rotation():
    """The published convention rotates pairs ``(2i, 2i + 1)`` of what a
    kernel's columns give; the module puts the COLUMNS in half-split
    order and rotates halves: the same numbers in another order, so
    every score (a sum over them) is the published one."""
    heads, plain, rope, theta = 3, 4, 8, 32e6
    ka, kw, kk = jax.random.split(jax.random.PRNGKey(4), 3)
    a = jax.random.normal(ka, (2, 9, 5))
    kernel = jax.random.normal(kw, (5, heads * (plain + rope)))
    positions = np.arange(9)[None] + np.asarray([[0], [5]])
    published = np.asarray(jnp.dot(a, kernel)).reshape(
        2, 9, heads, plain + rope)
    want = np.concatenate([published[..., :plain], _pairwise(
        published[..., plain:], positions, theta)], -1)
    got = np.asarray(sn.rotate(
        jnp.dot(a, sn.half_split(kernel, heads, rope)).reshape(
            published.shape), jnp.asarray(positions), theta, rope))
    order = np.r_[:plain, plain:plain + rope:2, plain + 1:plain + rope:2]
    np.testing.assert_allclose(got, want[..., order], atol=1e-5)
    # not the same numbers in place: the order is what differs
    assert np.abs(got - want).max() > 0.1
    key = jax.random.normal(kk, want.shape)
    np.testing.assert_allclose(
        (got * np.asarray(key)[..., order]).sum(-1),
        (want * np.asarray(key)).sum(-1), atol=1e-4)
    # ... and the plain reference's rotation is the published one
    np.testing.assert_allclose(
        joyai_net.rope_interleaved(
            jnp.asarray(published[0][..., plain:]), theta),
        want[0][..., plain:], atol=1e-5)


def _interleaved(x, positions, theta):
    """PR 40's rotation of pairs ``(2i, 2i + 1)`` on the activations."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions[..., None].astype(jnp.float32) * freq
    cos = jnp.repeat(jnp.cos(angle), 2, -1)[..., None, :]
    sin = jnp.repeat(jnp.sin(angle), 2, -1)[..., None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    partner = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(x.shape)
    return x * cos + partner * sin


def _parents_latent_attention(z, p, a):
    """PR 40's whole-window latent attention written out on the
    parameters AS PUBLISHED: the pairs rotated interleaved on the
    activations, ONE product of ``kv_b`` cut apart into keys and
    values, query blocks of plain XLA."""
    nope, rope, wide = z.head_dim, z.rope_dim, z.value_dim
    B, T = a.shape[:2]
    positions = jnp.arange(T)[None]
    cq = sn.rms_norm(a @ p["q_a"]["kernel"], p["q_norm"]["scale"], z.eps)
    q = (cq @ p["q_b"]["kernel"]).reshape(B, T, z.heads, nope + rope)
    kv_a = a @ p["kv_a"]["kernel"]
    latent = sn.rms_norm(
        kv_a[..., :z.latent_kv], p["kv_norm"]["scale"], z.eps)
    key = _interleaved(kv_a[..., None, z.latent_kv:], positions, z.rope_theta)
    q = jnp.concatenate([q[..., :nope], _interleaved(
        q[..., nope:], positions, z.rope_theta)], -1)
    kv = (latent @ p["kv_b"]["kernel"]).reshape(B, T, z.heads, nope + wide)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        key, (B, T, z.heads, rope))], -1)
    o = sn.blocked_attention(
        q[:, :, :, None], k, kv[..., nope:], 0, z.attention_block)
    return o.reshape(B, T, z.heads * wide) @ p["o"]["kernel"]


# two heads of 128 + 64 against values of 128 over 256 positions: whole
# lanes, so the chip's path (the kernels' bodies as plain JAX)
WIDE_HEADS = TINY._replace(
    heads=2, kv_heads=2, head_dim=128, rope_dim=64, value_dim=128,
    sequence_length=256, attention_block=128)


@pytest.mark.parametrize("sizes,path", [
    (TINY, "default"), (WIDE_HEADS, "chips_path")], ids=["tiny", "lane_wide"])
def test_a_window_and_every_leafs_gradient_equal_the_parents_layer(
        sizes, path, request):
    """Columns put half-split on the weight side, keys and values from
    two products, q rotated on its one pass to the kernel: the layer's
    output over a whole window and the gradient of EVERY leaf, in the
    published column order, are the parent's."""
    if path != "default":
        request.getfixturevalue(path)
        assert sn._fused_blocks(sizes.sequence_length, 192) is not None
    layer = sn.LatentAttention(sizes)
    ka, kp, kw = jax.random.split(jax.random.PRNGKey(11), 3)
    a = jax.random.normal(ka, (2, sizes.sequence_length, sizes.hidden))
    params = layer.init(kp, a)["params"]
    weight = jax.random.normal(kw, a.shape)

    def program(p, a):
        return layer.apply({"params": p}, a)[0]

    def parent(p, a):
        return _parents_latent_attention(sizes, p, a)

    def both(fn):
        return fn(params, a), jax.grad(
            lambda p, a: (fn(p, a) * weight).sum(), argnums=(0, 1))(params, a)

    (got, got_back), (want, want_back) = both(program), both(parent)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert jax.tree.structure(got_back) == jax.tree.structure(want_back)
    for g, w in zip(jax.tree.leaves(got_back), jax.tree.leaves(want_back)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=2e-5 * max(
            1.0, float(jnp.abs(w).max())))


def test_keys_and_values_by_two_products_equal_the_one_product_cut_apart():
    heads, nope, wide = 3, 8, 4
    kl, kw = jax.random.split(jax.random.PRNGKey(12))
    latent = jax.random.normal(kl, (2, 9, 6))
    kv_b = jax.random.normal(kw, (6, heads * (nope + wide)))
    kv = jnp.dot(latent, kv_b).reshape(2, 9, heads, nope + wide)
    by_head = kv_b.reshape(6, heads, nope + wide)
    np.testing.assert_allclose(jnp.einsum(
        "btc,chd->bthd", latent, by_head[..., :nope]), kv[..., :nope],
        atol=1e-5)
    np.testing.assert_allclose(jnp.einsum(
        "btc,chd->bthd", latent, by_head[..., nope:]), kv[..., nope:],
        atol=1e-5)


def test_the_parameters_keep_their_published_names_and_shapes(model):
    """A checkpoint and the benchmark's reference still fit: the
    permutation is of what ``__call__`` reads, not of what is kept."""
    for layer in TRUNK:
        name = "layer" if layer == "mtp" else None
        attn = model.params[layer][name]["attn"] if name \
            else model.params[layer]["attn"]
        assert jax.tree.map(lambda a: a.shape, attn) == {
            "kv_a": {"kernel": (64, 40)}, "kv_b": {"kernel": (32, 128)},
            "kv_norm": {"scale": (32,)}, "o": {"kernel": (64, 64)},
            "q_a": {"kernel": (64, 48)}, "q_b": {"kernel": (48, 96)},
            "q_norm": {"scale": (48,)}}


# -- the actor's side -------------------------------------------------------

def test_the_latent_cache_step_equals_the_sequence_pass_step_by_step(
        model):
    tokens = _tokens(seed=5)
    out = model.module.apply({"params": model.params}, tokens, None)
    hidden = model.init_hidden([2])
    # what a position IS, not every head's keys and values
    assert {k: v.shape[2:] for k, v in hidden.items() if k != "pos"} == {
        "latent": (TINY.sequence_length, TINY.latent_kv),
        "rope": (TINY.sequence_length, TINY.rope_dim)}
    stepped = []
    for t in range(TINY.sequence_length):
        o = model.module.apply({"params": model.params}, tokens[:, t], hidden)
        assert "mtp" not in o               # the actor drafts nothing
        hidden = o["hidden"]
        stepped.append(o["policy"])
        np.testing.assert_allclose(o["value"], out["value"][:, t], atol=5e-6)
    stepped = jnp.stack(stepped, 1)
    np.testing.assert_allclose(stepped, _logits(out["policy"]), atol=5e-6)
    assert int(hidden["pos"][0]) == TINY.sequence_length
    big = sn.sequence_net("joyai_flash_ep16")
    shapes = jax.eval_shape(lambda: big.init_hidden((1,)))
    assert sum(int(np.prod(s.shape[3:])) for k, s in shapes.items()
               if k != "pos") == 576


# -- the fused kernel at the latent heads' widths -----------------------------

# four heads, each with keys of its own, query-key heads of 192 against
# value heads of 128, 512 positions in blocks of 128
LATENT_HEADS = dict(B=1, T=512, H=4, D=192, Dv=128, block=128)


def test_the_fused_kernel_at_192_wide_heads_equals_blocked_attention():
    """The kernel's body run as plain JAX (``interpret``), float32, in
    the form the latent layers use it (each head its own key-value
    head, the query-key heads padded to whole lanes inside, the value
    heads narrower): output and the three gradients."""
    z = LATENT_HEADS
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(8), 4)
    q = jax.random.normal(kq, (z["B"], z["T"], z["H"], 1, z["D"]))
    k = jax.random.normal(kk, (z["B"], z["T"], z["H"], z["D"]))
    v = jax.random.normal(kv, (z["B"], z["T"], z["H"], z["Dv"]))
    weight = jax.random.normal(kw, q.shape[:-1] + (z["Dv"],))
    assert sn._fused_blocks(8192, z["D"]) == (sn.FUSED_BLOCK,
                                              sn.FUSED_COMPUTE)
    assert sn._fused_blocks(8192, 24) is None       # the tiny preset's

    def fused(q, k, v):
        return sn.fused_attention(q, k, v, 0, z["block"], interpret=True)

    def plain(q, k, v):
        return sn.blocked_attention(q, k, v, 0, z["block"])

    def scalar(attend):
        return lambda q, k, v: (attend(q, k, v) * weight).sum()

    assert fused(q, k, v).shape == weight.shape
    np.testing.assert_allclose(fused(q, k, v), plain(q, k, v), atol=1e-5)
    got = jax.grad(scalar(fused), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(scalar(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_the_step_of_the_new_preset_traces_twice(model, episodes):
    """PR 34's fault: whatever the first trace of the step made must
    serve the second (the cost harvest traces the step before the
    program compiles it)."""
    batch = jax.tree.map(jnp.asarray, _batch(episodes))
    batch["action_mask"] = jnp.zeros((4, 32, 1, 0))
    program = _program_loss(model, batch)
    texts = [jax.jit(jax.grad(lambda p: program(p)[0])).lower(
        model.params).as_text() for _ in range(2)]
    assert texts[0] == texts[1]
    assert "tpu_custom_call" not in texts[0]        # heads of 24: XLA's path


# -- the normal path ---------------------------------------------------------

def test_one_epoch_of_the_training_path_with_the_latent_preset(
        tmp_path, monkeypatch):
    """``main.py --train``'s path (``Learner(args).run()``): two actor
    processes play the token task through the one-token step and its
    latent cache, the learner trains whole sequences through the ring
    and the fused replay step, the module's term in the loss."""
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
