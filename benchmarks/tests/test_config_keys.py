"""What a configuration may state in place of harness code: who plays
its corpus, the count of its fused step, which layers hold stacked
kernels or the trunk's projections.  Absent, each is what the harness
did before the key existed,
so the configurations that are there read as they did."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import yaml

from benchmarks.harness import corpus, roofline, weights
from benchmarks.harness.cells import BENCH_DIR

CONFIGS = ("geese32", "geister_drc")


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".yaml")) as f:
        return yaml.safe_load(f)


def _module_shapes(config):
    from handyrl_tpu.environment import make_env, prepare_env
    from handyrl_tpu.models.wrapper import TPUModel

    prepare_env(config["env_args"])
    env = make_env(config["env_args"])
    env.reset()
    model = TPUModel(env.net())
    return weights.param_shapes(
        model.module, env.observation(env.players()[0]),
        model.init_hidden([1]))


# -- corpus.policy ----------------------------------------------------
def test_a_random_policy_corpus_is_uniform_and_runs_no_forward(monkeypatch):
    """``corpus: {policy: random}``: the environment's real episodes in
    the wire format, every recorded probability 1 / legal actions, and
    the net applied at most once (and then abstractly) however many
    steps were played."""
    from handyrl_tpu.batch import load_block
    from handyrl_tpu.models.geese_net import GeeseNet

    applies = []
    real_call = GeeseNet.__call__

    def counted(self, obs, hidden=None):
        applies.append(isinstance(obs, jax.core.Tracer))
        return real_call(self, obs, hidden)

    monkeypatch.setattr(GeeseNet, "__call__", counted)
    config = _config("geese32")
    config["corpus"]["policy"] = "random"
    config["train_args"]["lockstep_episodes"] = 4
    episodes = corpus._play(config, 6, 11)
    assert len(episodes) == 6 and applies == [True]
    steps = 0
    for episode in episodes:
        assert episode["steps"] >= 1
        for blob in episode["moment"]:
            for moment in load_block(blob):
                steps += 1
                for player in moment["turn"]:
                    legal = (np.asarray(
                        moment["action_mask"][player]) == 0).sum()
                    assert moment["selected_prob"][player] == \
                        pytest.approx(1.0 / legal)
    assert steps == sum(e["steps"] for e in episodes) > 6


def test_the_net_policy_is_the_default_and_keeps_its_cache_entry():
    names = {"geese32": "geese32-240001-384.pkl.z",
             "geister_drc": "geister_drc-240002-256.pkl.z"}
    for name, file in names.items():
        config = _config(name)
        assert "policy" not in config["corpus"]
        assert corpus.policy_of(config) == "net"
        assert corpus.cache_path(name, config) == os.path.join(
            BENCH_DIR, ".cache", "corpus", file)
        config["corpus"]["policy"] = "net"
        assert corpus.cache_path(name, config).endswith(file)
    config["corpus"]["policy"] = "random"
    assert corpus.cache_path("geister_drc", config).endswith(
        "geister_drc-240002-256-random.pkl.z")
    config["corpus"]["policy"] = "greedy"
    with pytest.raises(ValueError):
        corpus.cache_path("geister_drc", config)


# -- cost -------------------------------------------------------------
def test_a_new_configuration_brings_its_cost_as_a_file(tmp_path):
    """The twin of ``test_a_new_configuration_brings_its_reference_as_a_
    file``: a configuration naming ``cost: toy_cost`` is counted by
    that module, a new file of ``benchmarks/cost/`` (in ``tmp_path``
    here, put on the package's search path)."""
    import benchmarks.cost as package

    (tmp_path / "toy_cost.py").write_text(
        "def step_cost(param_shapes, train_args, geometry, ring_row_bytes):\n"
        "    return {'flops': 7.0 * train_args['batch_size'],\n"
        "            'bytes': float(ring_row_bytes + geometry['tokens'])}\n")
    config = dict(_config("geese32"), cost="toy_cost",
                  roofline={"tokens": 5})
    package.__path__.append(str(tmp_path))
    try:
        count = roofline.cost_function(config)
    finally:
        package.__path__.remove(str(tmp_path))
        sys.modules.pop("benchmarks.cost.toy_cost", None)
    assert count.__code__.co_filename == str(tmp_path / "toy_cost.py")
    assert count(None, config["train_args"], config["roofline"], 11) == {
        "flops": 7.0 * 256, "bytes": 16.0}


@pytest.mark.parametrize("name", CONFIGS)
def test_without_the_key_the_count_is_the_conv_dense_one(name):
    config = _config(name)
    assert "cost" not in config
    assert roofline.cost_function(config) is roofline.step_cost


# -- stacked_layers, trunk_layers -------------------------------------
def _make_params_before(shapes, seed, head_layers=(), stacked_layers=()):
    """``weights.make_params`` as it was before ``trunk_layers`` (with
    no ``stacked_layers`` given, as it was before that key too): the
    reference the default is held to, bit for bit."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            name = path[-1].key
            if name == "kernel":
                stack = any(getattr(k, "key", None) in stacked_layers
                            for k in path)
                z = z / math.sqrt(math.prod(leaf.shape[int(stack):-1]))
                if len(leaf.shape) == 2 or any(
                        getattr(k, "key", None) in head_layers
                        for k in path):
                    z = 0.1 * z
            elif name == "scale":
                z = 1.0 + 0.1 * z
            else:
                z = 0.1 * z
            out.append(z)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed))


def _assert_same_bits(now, before):
    assert jax.tree.structure(now) == jax.tree.structure(before)
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", CONFIGS)
def test_without_stacked_layers_the_weights_are_what_they_were(name):
    config = _config(name)
    assert not set(weights.LAYER_LISTS[1:]) & set(config)
    shapes = _module_shapes(config)
    heads = config.get("head_layers", ())
    now = weights.config_params(shapes, 2**31 + 5, config)
    _assert_same_bits(now, _make_params_before(shapes, 2**31 + 5, heads))


def test_without_trunk_layers_stacked_kernels_are_what_they_were():
    shapes = {"Experts_0": {"kernel": jax.ShapeDtypeStruct(
        (4, 8, 8), jnp.float32)},
        "Dense_0": {"kernel": jax.ShapeDtypeStruct((8, 8), jnp.float32),
                    "bias": jax.ShapeDtypeStruct((8,), jnp.float32)}}
    config = {"stacked_layers": ["Experts_0"], "head_layers": ["Dense_0"]}
    _assert_same_bits(
        weights.config_params(shapes, 9, config),
        _make_params_before(shapes, 9, ["Dense_0"], ["Experts_0"]))


def test_a_trunk_layers_dense_kernel_is_drawn_at_its_fan_in():
    """A 2-D kernel under a named layer (or under a module so named) is
    a projection of the trunk, ~ N(0, 1/fan-in); one outside stays a
    head's, ~ N(0, 0.01/fan-in).  The same draw either way (same
    ``fold_in`` index), and nothing else moves."""
    kernel = {"kernel": jax.ShapeDtypeStruct((64, 64), jnp.float32)}
    shapes = {"Block_0": {"attention": {"query": kernel, "out": kernel},
                          "LayerNorm_0": {"scale": jax.ShapeDtypeStruct(
                              (64,), jnp.float32)}},
              "mlp_up": kernel, "policy_head": kernel}
    plain = weights.make_params(shapes, 9)
    config = {"trunk_layers": ["Block_0", "mlp_up"]}
    trunk = weights.config_params(shapes, 9, config)
    for name, leaf in (("query", trunk["Block_0"]["attention"]["query"]),
                       ("out", trunk["Block_0"]["attention"]["out"]),
                       ("mlp_up", trunk["mlp_up"])):
        assert float(np.var(np.asarray(leaf["kernel"]))) == \
            pytest.approx(1 / 64, rel=0.1), name
    assert float(np.var(np.asarray(trunk["policy_head"]["kernel"]))) == \
        pytest.approx(0.01 / 64, rel=0.1)
    np.testing.assert_allclose(
        np.asarray(trunk["mlp_up"]["kernel"]),
        10.0 * np.asarray(plain["mlp_up"]["kernel"]), rtol=1e-6)
    _assert_same_bits(trunk["policy_head"], plain["policy_head"])
    _assert_same_bits(trunk["Block_0"]["LayerNorm_0"],
                      plain["Block_0"]["LayerNorm_0"])
    # a head named inside the trunk stays a head
    both = weights.make_params(shapes, 9, head_layers=["out"],
                               trunk_layers=["Block_0"])
    _assert_same_bits(both["Block_0"]["attention"]["out"],
                      plain["Block_0"]["attention"]["out"])


def test_a_stacked_kernel_takes_its_fan_in_from_the_middle_axes():
    shapes = {"Experts_0": {"kernel": jax.ShapeDtypeStruct(
        (4, 8, 8), jnp.float32)},
        "Conv_0": {"kernel": jax.ShapeDtypeStruct((4, 8, 8), jnp.float32)}}
    plain = weights.make_params(shapes, 9)
    stacked = weights.make_params(shapes, 9, stacked_layers=["Experts_0"])
    # the same draw (same fold_in index), scaled by 1/sqrt(8) not 1/sqrt(32)
    np.testing.assert_allclose(
        np.asarray(stacked["Experts_0"]["kernel"]),
        2.0 * np.asarray(plain["Experts_0"]["kernel"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(stacked["Conv_0"]["kernel"]),
                                  np.asarray(plain["Conv_0"]["kernel"]))
    many = weights.make_params(
        {"Experts_0": {"kernel": jax.ShapeDtypeStruct((64, 8, 64),
                                                      jnp.float32)}},
        9, stacked_layers=["Experts_0"])
    assert float(np.var(np.asarray(many["Experts_0"]["kernel"]))) == \
        pytest.approx(1 / 8, rel=0.05)
