"""The sparse-expert sequence policy at its tiny preset, seeded weights,
CPU, float32: the program against the plain reference
(``benchmarks/reference/trinity_net.py``), the chip's share of the
experts against the uncut layer, the actor's one-token step against the
learner's sequence pass, the window, the maskless wire and ring, the
chunked head, the refusals, and one epoch of ``main.py --train``'s path.
"""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import weights
from benchmarks.reference import training as ref_training
from benchmarks.reference import trinity_net, trinity_training
from handyrl_tpu.batch import make_batch
from handyrl_tpu.environment import make_env
from handyrl_tpu.generation import Generator
from handyrl_tpu.models import sequence_net as sn
from handyrl_tpu.models.wrapper import TPUModel
from handyrl_tpu.ops import losses
from handyrl_tpu.ops.update import make_apply_fn
from handyrl_tpu.staging import DeviceReplay, _run_geometry

TINY = sn.PRESETS["tiny"]
ENV_ARGS = {"env": "TokenTask", "net": "tiny"}
TRAIN = {
    "turn_based_training": False, "observation": True, "gamma": 1.0,
    "forward_steps": 32, "burn_in_steps": 0, "compress_steps": 4,
    "entropy_regularization": 0.01, "entropy_regularization_decay": 0.1,
    "lambda": 0.95, "policy_target": "TD", "value_target": "TD",
    "compute_dtype": "float32", "batch_size": 4,
}
LAYERS = [f"layer_{i}" for i in range(len(TINY.layer_types))]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture()
def tiny_geometry(monkeypatch):
    """The plain reference told the tiny preset's geometry (what the
    weights' shapes do not say)."""
    for key, value in {
            "layer_types": TINY.layer_types, "num_dense_layers": 1,
            "sliding_window": TINY.window, "query_block": 16,
            "num_experts_per_tok": TINY.experts_per_token}.items():
        monkeypatch.setitem(trinity_net.GEOMETRY, key, value)


@pytest.fixture(scope="module")
def model():
    net = TPUModel(sn.sequence_net("tiny"))
    shapes = weights.param_shapes(net.module, np.int32(0),
                                  net.init_hidden([1]))
    net.params = weights.make_params(shapes, 7, (), ["experts"], LAYERS)
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


def _logits(out):
    return out["policy"].features @ out["policy"].kernel


# -- the net against the plain reference ----------------------------------

def test_logits_and_value_equal_the_plain_reference(model, tiny_geometry):
    tokens = _tokens()
    out = model.module.apply({"params": model.params}, tokens, None)
    ref = trinity_net.forward(model.params, tokens)
    np.testing.assert_allclose(_logits(out), ref["policy"], atol=2e-6)
    np.testing.assert_allclose(out["value"], ref["value"], atol=2e-6)
    assert out["counts"]["expert_load"].shape == (2, TINY.experts_held)


def _batch(episodes):
    columns = [trinity_training.episode_columns(ep) for ep in episodes]
    return trinity_training.gather(
        columns, [0, 1, 2, 3], [0] * 4, [0] * 4, 32, 0, True)


def test_loss_and_every_gradient_leaf_equal_the_plain_reference(
        model, episodes, tiny_geometry):
    batch = _batch(episodes)
    cfg = losses.LossConfig.from_config(TRAIN)
    apply_fn = make_apply_fn(model, "float32")

    def program(params):
        device = dict(jax.tree.map(jnp.asarray, batch),
                      action_mask=jnp.zeros((4, 32, 1, 0)))
        out, _ = losses.compute_loss(apply_fn, params, device,
                                     losses.SEQUENCE, cfg)
        return out["total"], out

    def reference(params):
        return sum(trinity_training.loss(
            trinity_net, params, jax.tree.map(lambda a: a[b:b + 1], batch),
            TRAIN)[0] for b in range(4))

    (total, parts), grads = jax.value_and_grad(program, has_aux=True)(
        model.params)
    ref_total, ref_grads = jax.value_and_grad(reference)(model.params)
    np.testing.assert_allclose(total, ref_total, rtol=1e-5)
    assert 0 < float(parts["window_fill"]) < 1
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, ours), theirs in zip(flat, jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(theirs).max()) > 0, path
        np.testing.assert_allclose(
            ours, theirs, rtol=2e-4, atol=2e-5 * float(jnp.abs(theirs).max()),
            err_msg=jax.tree_util.keystr(path))


def test_the_one_sequence_loss_is_the_general_reference_loss(
        model, episodes, tiny_geometry):
    """``trinity_training.loss`` (one sequence, the targets a scan) is
    ``training.loss`` (any batch, the targets a Python loop) on the
    same sequence: total, parts and gradient."""
    class OneSequence:
        RECURRENT = False

        @staticmethod
        def forward(params, obs, hidden, lowp):
            return trinity_net.sequence(params, obs, lowp)

    row = jax.tree.map(lambda a: a[1:2], _batch(episodes))

    def general(params):
        return ref_training.loss(OneSequence, params, dict(
            row, action_mask=jnp.zeros((1, 1, 1, 1))), TRAIN)

    def ours(params):
        return trinity_training.loss(trinity_net, params, row, TRAIN)

    (want, want_parts), want_grad = jax.value_and_grad(
        general, has_aux=True)(model.params)
    (got, got_parts), got_grad = jax.value_and_grad(
        ours, has_aux=True)(model.params)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert set(got_parts) == set(want_parts) == {"p", "v", "ent"}
    for key in want_parts:
        np.testing.assert_allclose(got_parts[key], want_parts[key],
                                   rtol=1e-6)
    for a, b in zip(jax.tree.leaves(got_grad), jax.tree.leaves(want_grad)):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-6 * float(jnp.abs(b).max()))


# an expert layer of the tiny preset's form at lane-wide widths, which
# the grouped path takes: 2 x 128 positions x 2 picks are one tile of rows
LANE_WIDE = TINY._replace(hidden=128, expert_width=128)


@pytest.mark.parametrize("body", ["dense", "grouped"])
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(
        body, tiny_geometry, request):
    """Every chip's held experts' part, the shared expert counted once,
    is what the uncut reference gives for the whole layer: by the dense
    stack at the tiny preset's widths, and by the grouped products'
    bodies at lane-wide ones."""
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
    uncut = trinity_net.experts(
        m.reshape(-1, z.hidden), params, None, trinity_net.GEOMETRY)
    shared = trinity_net.swiglu(
        m.reshape(-1, z.hidden), *(params["shared"][k]["kernel"]
                                   for k in ("w1", "w3", "w2")), None)
    total, loads = 0.0, []
    for share in range(shares):
        first = share * z.experts_held
        held = dict(params, experts=jax.tree.map(
            lambda k: k[first:first + z.experts_held],
            params["experts"]))
        y, load = sn.SparseExperts(
            z._replace(first_expert=first)).apply({"params": held}, m)
        total = total + y.reshape(-1, z.hidden) - shared
        loads.append(load)
    np.testing.assert_allclose(total + shared, uncut, atol=5e-6)
    # every pick fell on some chip's expert
    assert int(sum(l.sum() for l in loads)) == (
        2 * positions * z.experts_per_token)


def test_padding_takes_no_expert_and_moves_no_real_position(model):
    """A window's positions past its episode's end go in as -1: they
    are routed nowhere, and what the real positions give is what it
    was (causal: nothing real looks at them)."""
    tokens = _tokens(seed=9)
    real = 20
    padded = tokens.at[:, real:].set(-1)
    whole = model.module.apply({"params": model.params}, tokens, None)
    cut = model.module.apply({"params": model.params}, padded, None)
    np.testing.assert_allclose(_logits(cut)[:, :real],
                               _logits(whole)[:, :real], atol=2e-6)
    np.testing.assert_allclose(cut["value"][:, :real],
                               whole["value"][:, :real], atol=2e-6)
    cut, whole = cut["counts"], whole["counts"]
    assert float(cut["expert_picks"]) == 2 * real * TINY.experts_per_token
    assert float(whole["expert_picks"]) == tokens.size * TINY.experts_per_token
    assert (cut["expert_load"] <= whole["expert_load"]).all()
    assert int(cut["expert_load"].sum()) < int(whole["expert_load"].sum())
    head = model.module.apply({"params": model.params}, tokens[:, :real],
                              None)
    np.testing.assert_array_equal(cut["expert_load"],
                                  head["counts"]["expert_load"])


# -- the actor's side -------------------------------------------------------

def test_the_cached_step_equals_the_sequence_pass_position_by_position(
        model):
    """...so the behaviour probability an actor records is the one the
    learner recomputes."""
    tokens = _tokens(seed=5)
    out = model.module.apply({"params": model.params}, tokens, None)
    hidden = model.init_hidden([2])
    stepped = []
    for t in range(TINY.sequence_length):
        o = model.module.apply({"params": model.params}, tokens[:, t], hidden)
        hidden = o["hidden"]
        stepped.append(o["policy"])
        np.testing.assert_allclose(o["value"], out["value"][:, t], atol=2e-6)
    stepped = jnp.stack(stepped, 1)
    np.testing.assert_allclose(stepped, _logits(out), atol=2e-6)
    actions = _tokens(seed=6)[..., None]
    recorded = jnp.take_along_axis(jax.nn.softmax(stepped), actions, -1)
    learner, _ = losses.policy_terms(out["policy"], actions)
    np.testing.assert_allclose(jnp.exp(learner), recorded, rtol=1e-5)
    assert int(hidden["pos"][0]) == TINY.sequence_length


@pytest.mark.parametrize("kind,moved", [(sn.SLIDING, False), (sn.FULL, True)])
def test_a_key_beyond_the_window_moves_only_a_full_layer(kind, moved):
    attention = sn.Attention(TINY, kind)
    a = jax.random.normal(jax.random.PRNGKey(2), (1, 32, TINY.hidden))
    params = attention.init(jax.random.PRNGKey(3), a)
    far = TINY.window + 3           # position 0 is out of its window
    base = attention.apply(params, a)[0]
    other = attention.apply(params, a.at[:, 0].add(1.0))[0]
    changed = float(jnp.abs(base - other)[0, far:].max())
    assert (changed > 1e-4) == moved, changed
    # inside the window either kind sees it
    assert float(jnp.abs(base - other)[0, 1:TINY.window].max()) > 1e-4


# -- the fused kernel against the path it replaces --------------------------

# lane-wide and small: two key-value heads of two query heads each, 128
# wide, a window of 256 in 512 positions, the kernel in blocks of 128
FUSED = dict(B=1, T=512, KV=2, G=2, D=128, window=256, block=128)


def _qkv(seed=4, **over):
    z = dict(FUSED, **over)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (z["B"], z["T"], z["KV"], z["G"], z["D"])),
            jax.random.normal(kk, (z["B"], z["T"], z["KV"], z["D"])),
            jax.random.normal(kv, (z["B"], z["T"], z["KV"], z["D"])))


@pytest.mark.parametrize("window", [FUSED["window"], 0],
                         ids=["window_layer", "full_layer"])
def test_the_fused_kernel_equals_blocked_attention_out_and_back(window):
    """The kernel's body run as plain JAX (``interpret``), float32:
    output and the gradients of q, k and v against the XLA path's."""
    q, k, v = _qkv()
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def scalar(attend):
        return lambda q, k, v: (attend(q, k, v) * weight).sum()

    def fused(q, k, v):
        return sn.fused_attention(q, k, v, window, FUSED["block"],
                                  interpret=True)

    def plain(q, k, v):
        return sn.blocked_attention(q, k, v, window, FUSED["block"])

    np.testing.assert_allclose(fused(q, k, v), plain(q, k, v), atol=1e-5)
    got = jax.grad(scalar(fused), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(scalar(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


# -- one pass an operand between its projection and the kernel ---------------

@pytest.mark.parametrize("heads,width,turn,gained", [
    (3, 128, sn.Turn(128, 10000.0, 1e-5), True),
    (3, 128, sn.Turn(0, 10000.0, 1e-5), True),
    (2, 192, sn.Turn(64, 32e6), False)],
    ids=["window_layer", "full_layer", "latent_query"])
def test_the_kernel_pass_equals_norm_rotation_and_scale_in_plain_xla(
        heads, width, turn, gained):
    """``_turn_pass`` (its bodies run as plain JAX, float32) against
    ``turned`` times the scale, transposed to the kernels' layout: the
    result, and through its ``custom_vjp`` the gradients of the operand
    and of the norm's scale.  Heads of one tile are read as the
    projection wrote them; a head of 192 is two tiles, the second half
    full, its rotary numbers in halves of 32 lanes."""
    B, T, rows, scale = 2, 256, 128, 0.25
    kx, kg, kw = jax.random.split(jax.random.PRNGKey(21), 3)
    x = jax.random.normal(kx, (B, T, heads, width))
    gain = 1 + 0.1 * jax.random.normal(kg, (width,)) if gained else None
    weight = jax.random.normal(kw, (B, heads, T, width))

    def kernel(x, gain):
        return sn._turn_pass(x, turn, gain, scale, rows, True)

    def plain(x, gain):
        return (sn.turned(x, turn, gain) * scale).transpose(0, 2, 1, 3)

    def both(fn):
        return fn(x, gain), jax.grad(
            lambda x, gain: (fn(x, gain) * weight).sum(),
            argnums=(0, 1) if gained else 0)(x, gain)

    (got, got_back), (want, want_back) = both(kernel), both(plain)
    assert got.shape == (B, heads, T, width)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_back), jax.tree.leaves(want_back)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-4)


# four query heads on two key-value heads of 128, a window of 128 in 256
# positions: whole lanes, so the chip's path where it is asked for
WIDE_HEADS = TINY._replace(heads=4, kv_heads=2, head_dim=128, window=128,
                          sequence_length=256, attention_block=128)


@pytest.mark.parametrize("kind", [sn.SLIDING, sn.FULL])
def test_a_layer_by_the_chips_path_equals_the_default_path(kind, request):
    """The whole attention layer over a window, norm and rotation on
    each operand's one pass to the fused kernel (the kernels' bodies as
    plain JAX), against the same layer in plain XLA: the output and the
    gradient of every leaf, the two norms' scales among them."""
    layer = sn.Attention(WIDE_HEADS, kind)
    ka, kp, kw = jax.random.split(jax.random.PRNGKey(22), 3)
    a = jax.random.normal(ka, (2, WIDE_HEADS.sequence_length, WIDE_HEADS.hidden))
    params = layer.init(kp, a)["params"]
    weight = jax.random.normal(kw, a.shape)

    def both():
        def out(p, a):
            return layer.apply({"params": p}, a)[0]
        return out(params, a), jax.grad(
            lambda p, a: (out(p, a) * weight).sum(), argnums=(0, 1))(params, a)

    want, want_back = both()
    request.getfixturevalue("chips_path")
    got, got_back = both()
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert jax.tree.structure(got_back) == jax.tree.structure(want_back)
    for g, w in zip(jax.tree.leaves(got_back), jax.tree.leaves(want_back)):
        np.testing.assert_allclose(g, w, atol=2e-5 * max(
            1.0, float(jnp.abs(w).max())))


def test_the_attention_parameters_keep_their_names_and_shapes(model):
    """The norms' scales live where ``RMSNorm`` kept them, though the
    norm itself now runs on the operand's way to the kernel."""
    for layer in LAYERS:
        assert jax.tree.map(
            lambda a: a.shape, model.params[layer]["attn"]) == {
                "gate": {"kernel": (64, 64)}, "k": {"kernel": (64, 32)},
                "k_norm": {"scale": (16,)}, "o": {"kernel": (64, 64)},
                "q": {"kernel": (64, 64)}, "q_norm": {"scale": (16,)},
                "v": {"kernel": (64, 32)}}


@pytest.mark.parametrize("distance", [
    FUSED["window"] - 1, FUSED["window"], FUSED["window"] + 1])
def test_the_kernels_mask_is_the_layers_own_rule_at_the_windows_edge(
        distance):
    """One key moved at ``distance`` from a query moves that query in a
    window layer exactly where ``_visible`` says the key is seen."""
    q, k, v = _qkv(seed=11)
    window, t = FUSED["window"], 400      # across two of the kernel's blocks
    s = t - distance

    def out(k, v):
        return sn.fused_attention(q, k, v, window, FUSED["block"],
                                  interpret=True)[0, t]

    moved = float(jnp.abs(
        out(k.at[:, s].add(1.0), v.at[:, s].add(1.0)) - out(k, v)).max())
    seen = bool(sn._visible(t, s, window))
    assert seen == (distance < window)
    assert (moved > 1e-4) == seen, moved


def test_a_kernel_first_asked_for_inside_a_trace_serves_the_next_trace():
    """Every later trace is handed the same kernel (its tables of which
    blocks to visit are made once): were its arrays tracers of the
    first trace, the second would fail with ``UnexpectedTracerError``,
    as the program's second trace of its step did on the chip."""
    q, k, v = _qkv()
    for _ in range(2):              # two functions: two traces
        out = jax.jit(lambda q, k, v: sn.window_attention(
            q, k, v, FUSED["window"], 128))(q, k, v)
    np.testing.assert_allclose(
        out, sn.blocked_attention(q, k, v, FUSED["window"], 128), atol=2e-6)
    made = []
    jax.jit(lambda x: made.append(
        sn._fused_kernel(256, 128, 2, 128, 128, False)) or x).lower(1.0)
    leaves = jax.tree.leaves(made[0])
    assert leaves and not any(
        isinstance(leaf, jax.core.Tracer) for leaf in leaves)


def test_the_tiny_preset_and_a_cpu_lowering_take_the_xla_path(model):
    """Which path runs is read from what the program can observe: a
    head narrower than a lane never reaches the kernel, and at
    lane-wide shapes a program lowered for the CPU holds none."""
    tokens = _tokens()
    tiny = jax.jit(lambda p, x: _logits(
        model.module.apply({"params": p}, x, None))).lower(
            model.params, tokens).as_text()
    assert "tpu_custom_call" not in tiny and "stablehlo.case" not in tiny
    q, k, v = _qkv()
    wide = jax.jit(jax.grad(lambda q, k, v: sn.window_attention(
        q, k, v, FUSED["window"], 128).sum(), argnums=(0, 1, 2)))
    assert "tpu_custom_call" not in wide.lower(q, k, v).as_text()
    np.testing.assert_array_equal(
        sn.window_attention(q, k, v, FUSED["window"], 128),
        sn.blocked_attention(q, k, v, FUSED["window"], 128))


# -- the grouped products against the dense stack they replace -------------

# lane-wide and small: 256 positions of 4 picks (two tiles of 512 rows),
# 4 of 16 experts held, 256 wide against experts of 128
GROUPED = dict(N=256, k=4, held=4, experts=16, d=256, f=128)


def _routing(case):
    """``selected (N, k)``, ``first_expert`` and ``valid (N,)`` of a
    case, and how many picks it must leave live (None: as they fall)."""
    z = GROUPED
    N, k, held, E = z["N"], z["k"], z["held"], z["experts"]
    n, j = jnp.arange(N)[:, None], jnp.arange(k)[None]
    drawn = jax.lax.top_k(
        jax.random.uniform(jax.random.PRNGKey(12), (N, E)), k)[1]
    valid = jnp.ones((N,), bool)
    if case == "even":                  # every expert as often as any
        return (n + j * (E // k)) % E, 0, valid, N * k * held // E
    if case == "one_expert":            # every position on held expert 2
        return jnp.broadcast_to(
            jnp.asarray([E - 1, 2, E - 2, E - 3]), (N, k)), 0, valid, N
    if case == "none_held":
        return jnp.broadcast_to(held + j, (N, k)), 0, valid, 0
    if case == "all_held":              # the worst case fills the buffer
        return (n + j) % held, 0, valid, N * k
    if case == "held_in_the_middle":    # experts 6-9: routed below, above
        assert int((drawn < 6).sum()) and int((drawn > 9).sum())
        return drawn, 6, valid, None
    if case == "padded_tail":
        return drawn, 0, valid.at[N - 56:].set(False), None
    assert case == "part_of_a_tile"
    return drawn, 0, valid, None


@pytest.mark.parametrize("case", [
    "even", "one_expert", "none_held", "all_held", "held_in_the_middle",
    "padded_tail", "part_of_a_tile"])
def test_the_grouped_products_equal_the_dense_stack_out_and_back(case):
    """The kernels' bodies run as plain JAX (``interpret``), float32:
    the output and the gradient of every operand (``m``, the router's
    ``weights``, each expert's three kernels) against the dense
    stack's, and the positions counted for each held expert."""
    z = GROUPED
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    m = jax.random.normal(keys[0], (z["N"], z["d"]))
    kernels = tuple(
        jax.random.normal(key, shape) / np.sqrt(shape[1])
        for key, shape in zip(keys[1:4], [
            (z["held"], z["d"], z["f"]), (z["held"], z["d"], z["f"]),
            (z["held"], z["f"], z["d"])]))
    weights_ = jax.random.uniform(keys[4], (z["N"], z["k"]), minval=0.1)
    weight = jax.random.normal(keys[5], m.shape)
    selected, first, valid, live = _routing(case)
    sizes = TINY._replace(experts=z["experts"], experts_held=z["held"],
                          first_expert=first, experts_per_token=z["k"])
    here = (selected[..., None] == first + jnp.arange(z["held"])) \
        & valid[:, None, None]
    if live is not None:
        assert int(here.sum()) == live
    if case == "part_of_a_tile":
        assert int(here.sum()) % sn.GROUPED_ROWS
    assert sn._grouped_tiles(z["N"] * z["k"], z["d"], z["f"])

    def grouped(*operands):
        return sn.grouped_experts(*operands, interpret=True)

    def both(experts):
        def scalar(m, weights_, *kernels):
            return (experts(m, here, weights_, *kernels) * weight).sum()
        return (experts(m, here, weights_, *kernels),
                jax.grad(scalar, argnums=range(5))(m, weights_, *kernels))

    (got, got_back), (want, want_back) = both(grouped), both(sn.dense_experts)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, w in zip(got_back, want_back):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5)
    out, counts = sn.held_experts(
        m, selected, weights_, kernels, sizes, valid)
    np.testing.assert_array_equal(out, want)    # a CPU lowering: dense
    np.testing.assert_array_equal(counts, here.sum((0, 1)))
    if case == "none_held":
        assert not np.asarray(got).any()
        assert not any(np.asarray(g).any() for g in got_back[2:])
    if case == "padded_tail":
        # rows that are not valid take no expert and move nothing real
        moved = grouped(m.at[~valid].add(1.0), here, weights_, *kernels)
        np.testing.assert_array_equal(moved[valid], got[valid])
        assert not np.asarray(got[~valid]).any()
        assert not np.asarray(got_back[0][~valid]).any()


def test_the_grouped_path_serves_the_second_trace_of_a_step(chips_path):
    """PR 34's fault, held for this kernel too: tier-1 and
    ``step_profile()`` trace the step twice, and whatever the first
    trace built must serve the second."""
    layer = sn.SparseExperts(LANE_WIDE)
    m = jax.random.normal(jax.random.PRNGKey(0), (2, 128, LANE_WIDE.hidden))
    params = layer.init(jax.random.PRNGKey(1), m)

    def loss(params, m):
        y, counts = layer.apply(params, m)
        return (y * y).sum(), counts

    texts, got = [], []
    for _ in range(2):                  # two functions: two traces
        step = jax.jit(jax.value_and_grad(
            lambda p, m: loss(p, m), has_aux=True))
        texts.append(step.lower(params, m).as_text())
        got.append(step(params, m))
    assert texts[0] == texts[1]
    for a, b in zip(jax.tree.leaves(got[0]), jax.tree.leaves(got[1])):
        np.testing.assert_array_equal(a, b)


def test_a_whole_net_by_the_grouped_body_equals_the_dense_body(request):
    """The net's whole-window pass with rematerialised layers, padding
    past an episode's end and every gradient leaf: the chip's path of
    the expert layers (bodies as plain JAX) against the dense stack."""
    net = sn.SequencePolicyNet(LANE_WIDE._replace(sequence_length=128))
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (2, 128), 0, TINY.vocab).at[1, 100:].set(-1)
    params = net.init(jax.random.PRNGKey(3), tokens, None)

    def loss(params):
        out = net.apply(params, tokens, None)
        return (jnp.square(_logits(out)).mean() + out["value"].sum(),
                out["counts"]["expert_load"])

    want = jax.value_and_grad(loss, has_aux=True)(params)
    request.getfixturevalue("chips_path")
    got = jax.value_and_grad(loss, has_aux=True)(params)
    np.testing.assert_array_equal(got[0][1], want[0][1])
    assert int(got[0][1].sum()) > 0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


def test_the_tiny_presets_and_a_cpu_lowering_take_the_dense_stack(model):
    """Which path runs is read from what the program can observe: an
    expert narrower than a lane, or rows that are no whole tile (the
    actors' one-token step), never reach the grouped products, and at
    lane-wide shapes a program lowered for the CPU holds none."""
    for preset in ("tiny", "tiny_latent"):
        z = sn.PRESETS[preset]
        assert sn._grouped_tiles(
            4 * 32 * z.experts_per_token, z.hidden, z.expert_width) is None
    for preset in ("trinity_mini_ep8", "joyai_flash_ep16"):
        z = sn.PRESETS[preset]
        assert sn._grouped_tiles(
            8192 * z.experts_per_token, z.hidden, z.expert_width) == {
                z.hidden: 1024, z.expert_width: min(1024, z.expert_width)}
        # one token a seat: the dense stack
        assert sn._grouped_tiles(
            4 * z.experts_per_token, z.hidden, z.expert_width) is None
    layer = sn.SparseExperts(LANE_WIDE)
    m = jax.random.normal(jax.random.PRNGKey(0), (2, 128, LANE_WIDE.hidden))
    params = layer.init(jax.random.PRNGKey(1), m)
    wide = jax.jit(jax.grad(
        lambda p, m: layer.apply(p, m)[0].sum())).lower(params, m).as_text()
    assert "tpu_custom_call" not in wide


# -- what a rematerialised layer keeps -----------------------------------------

def _products(jaxpr, lhs_shape, rhs_shape):
    """The products ``x W`` of ``lhs_shape`` by ``rhs_shape`` (``x``'s
    last axis against ``W``'s first) anywhere in ``jaxpr``, the jaxprs
    its equations hold among them."""
    found = 0
    x_w = (((len(lhs_shape) - 1,), (0,)), ((), ()))
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "dot_general"
                and eqn.params["dimension_numbers"] == x_w
                and tuple(v.aval.shape for v in eqn.invars)
                == (lhs_shape, rhs_shape)):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _products(sub, lhs_shape, rhs_shape)
    return found


@pytest.mark.parametrize("preset", ["tiny", "tiny_latent", "tiny_hybrid"])
def test_a_rematerialised_layer_keeps_its_widest_products(preset, monkeypatch):
    """Loss and every gradient leaf of a whole-window pass with the
    named products kept across ``RematLayer`` equal the same net's with
    no rematerialisation at all, and the gradient multiplies by no
    kept product's weights a second time: ``x w1`` and ``x w3`` of a
    dense layer and of a shared expert, a delta mixer's q and k."""
    z = sn.PRESETS[preset]
    net = sn.SequencePolicyNet(z)
    B, T = 2, z.sequence_length
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (B, T), 0, z.vocab).at[1, T - 6:].set(-1)
    params = net.init(jax.random.PRNGKey(3), tokens, None)

    def loss(params):
        out = net.apply(params, tokens, None)
        heads = [out["policy"]] + ([out["mtp"]] if "mtp" in out else [])
        return sum(jnp.square(p.features @ p.kernel).mean()
                   for p in heads) + out["value"].sum()

    kept = jax.value_and_grad(loss)(params)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
    monkeypatch.setattr(sn, "RematLayer", sn.Layer)
    plain = jax.value_and_grad(loss)(params)
    np.testing.assert_allclose(kept[0], plain[0], rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(kept[1])
    for (path, ours), theirs in zip(flat, jax.tree.leaves(plain[1])):
        assert float(jnp.abs(theirs).max()) > 0, path
        np.testing.assert_allclose(
            ours, theirs, rtol=1e-5, atol=1e-6 * float(jnp.abs(theirs).max()),
            err_msg=jax.tree_util.keystr(path))
    # w1 and w3 are one shape: two products a SwiGLU going forward, and
    # none of that shape coming back (dx and dw contract other axes)
    dense = _products(jaxpr, (B, T, z.hidden), (z.hidden, z.dense_width))
    assert dense == 2 * z.dense_layers, dense
    sparse = len(z.layer_types) - z.dense_layers + z.nextn_modules
    shared = _products(jaxpr, (B * T, z.hidden),
                       (z.hidden, z.expert_width * z.shared_experts))
    assert shared == 2 * sparse, shared
    if sn.LINEAR in z.layer_types:
        held, _ = sn.held_heads(z)
        delta = _products(jaxpr, (B, T, z.hidden),
                          (z.hidden, held * z.delta_key_dim))
        assert delta == 2 * z.layer_types.count(sn.LINEAR), delta


# -- wire, ring and gather without a mask -----------------------------------

def test_an_all_legal_episode_crosses_wire_ring_and_gather_without_a_mask(
        episodes):
    from handyrl_tpu.batch import load_block

    moment = load_block(episodes[0]["moment"][-1])[-1]
    assert moment["action_mask"][0].shape == (0,)      # nothing listed
    replay = DeviceReplay(dict(TRAIN), 8, 64 << 20)
    replay.offer(episodes)
    replay.ingest()
    # the token rides the packed int32 channel: no channel of its own,
    # and no word of mask beyond the two seat bits
    assert replay.buffers["obs"] is None
    assert replay.buffers["steps"].shape[1] == 5 + 1 + 1 + 1
    slots = np.arange(len(episodes), dtype=np.int32)
    zeros = np.zeros(len(episodes), np.int32)
    got = jax.device_get(replay._sample_fn(
        replay.buffers, jnp.asarray(slots), jnp.asarray(zeros),
        jnp.asarray(zeros)))
    random.seed(0)
    want = make_batch(
        [dict(ep, start=0, end=ep["steps"], base=0, train_start=0,
              total=ep["steps"]) for ep in episodes], TRAIN)
    assert got["action_mask"].shape == want["action_mask"].shape == (
        len(episodes), 32, 1, 0)
    assert set(got) == set(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    assert got["observation"].dtype == np.int32
    # context is read, not acted on: no turn where the prompt is
    assert (want["turn_mask"].sum(1) < want["observation_mask"].sum(1)).all()


def test_a_sequence_window_bounds_the_append_programs(monkeypatch):
    """One append program per ``_RUN_ROUND`` rows up to ``_MAX_RUN``
    slots: 4 at a 4,096-step window, where the board games' geometry
    would make 128; theirs is what it was."""
    import handyrl_tpu.staging as staging

    assert _run_geometry(8) == _run_geometry(12) == (256, 8)
    assert _run_geometry(4096) == (4096, 4)
    monkeypatch.setattr(staging, "_RUN_ROUND", staging._RUN_ROUND)
    monkeypatch.setattr(staging, "_MAX_RUN", staging._MAX_RUN)
    replay = DeviceReplay(dict(TRAIN, forward_steps=4096), 8, 64 << 20)
    assert (staging._RUN_ROUND, staging._MAX_RUN) == (4096, 4)
    assert staging._MAX_RUN * replay.t_max // staging._RUN_ROUND == 4


# -- the head in chunks -------------------------------------------------------

def test_the_chunked_head_equals_the_unchunked(monkeypatch):
    monkeypatch.setattr(losses, "POLICY_CHUNK", 8)
    feats = jax.random.normal(jax.random.PRNGKey(0), (3, 7, 1, 16))
    kernel = jax.random.normal(jax.random.PRNGKey(1), (16, 50))
    actions = jax.random.randint(jax.random.PRNGKey(2), (3, 7, 1, 1), 0, 50)
    logits = feats @ kernel

    def chunked(feats, kernel):
        selected, entropy = losses.policy_terms(
            losses.FactoredPolicy(feats, kernel), actions)
        return selected.sum() + 2 * entropy.sum(), (selected, entropy)

    def whole(feats, kernel):
        selected, _ = losses.policy_terms(feats @ kernel, actions)
        entropy = losses._masked_entropy(feats @ kernel)
        return selected.sum() + 2 * entropy.sum(), (selected, entropy)

    (_, ours), g_ours = jax.value_and_grad(chunked, (0, 1), has_aux=True)(
        feats, kernel)
    (_, theirs), g_theirs = jax.value_and_grad(whole, (0, 1), has_aux=True)(
        feats, kernel)
    assert ours[0].shape == (3, 7, 1, 1) and ours[1].shape == (3, 7, 1)
    for a, b in zip(ours + g_ours, theirs + g_theirs):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert logits.shape[-1] == 50


# -- what is refused at build ---------------------------------------------------

def _learner_args(**train):
    return {
        "env_args": dict(ENV_ARGS),
        "train_args": dict(
            TRAIN, update_episodes=12, minimum_episodes=8,
            maximum_episodes=64, epochs=1, num_batchers=1, eval_rate=0.0,
            worker={"num_parallel": 2}, seed=2, batch_size=2, **train),
        "worker_args": {"num_parallel": 2, "server_address": ""},
    }


@pytest.mark.parametrize("train,sentence", [
    ({"burn_in_steps": 2}, "burn_in_steps must be 0"),
    ({"forward_steps": 16}, "must hold the whole sequence"),
    ({"mesh": {"dp": 2}}, "no axis to be divided over"),
])
def test_what_a_sequence_net_is_refused_with(train, sentence, tmp_path,
                                             monkeypatch):
    from handyrl_tpu.learner import Trainer

    monkeypatch.chdir(tmp_path)
    args = _learner_args(**train)["train_args"]
    env = make_env(ENV_ARGS)
    net = TPUModel(env.net())
    net.init_params(env.observation(0), seed=0)
    with pytest.raises(ValueError, match=sentence):
        Trainer(args, net)


# -- the normal path ---------------------------------------------------------------

def test_one_epoch_of_the_training_path_ends_with_a_saved_model(
        tmp_path, monkeypatch):
    """``main.py --train``'s path (``Learner(args).run()``) with the
    tiny preset: two actor processes play the token task through the
    one-token step and its cache, the learner trains whole sequences
    through the ring and the fused replay step."""
    from handyrl_tpu.learner import Learner

    monkeypatch.chdir(tmp_path)
    learner = Learner(_learner_args(metrics_path="metrics.jsonl"))
    assert learner.trainer._replay_step is not None
    assert learner.trainer.train_mesh is None
    learner.run()
    assert learner.trainer.failure is None
    assert learner.model_epoch == 1 and learner.trainer.steps > 0
    assert os.path.exists(tmp_path / "models" / "1.ckpt")
    replay = learner.trainer.device_replay
    assert replay.buffers["obs"] is None and replay.num_actions == 0
