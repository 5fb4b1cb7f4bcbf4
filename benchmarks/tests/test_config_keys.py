"""What a configuration may state in place of harness code: who plays
its corpus, the count of its fused step, which layers hold stacked
kernels.  Absent, each is what the harness did before the key existed,
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


# -- stacked_layers ---------------------------------------------------
def _make_params_before(shapes, seed, head_layers=()):
    """``weights.make_params`` as it was before ``stacked_layers``: the
    reference the default is held to, bit for bit."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            name = path[-1].key
            if name == "kernel":
                z = z / math.sqrt(math.prod(leaf.shape[:-1]))
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


@pytest.mark.parametrize("name", CONFIGS)
def test_without_stacked_layers_the_weights_are_what_they_were(name):
    config = _config(name)
    assert "stacked_layers" not in config
    shapes = _module_shapes(config)
    heads = config.get("head_layers", ())
    now = weights.make_params(shapes, 2**31 + 5, heads,
                              config.get("stacked_layers", ()))
    before = _make_params_before(shapes, 2**31 + 5, heads)
    assert jax.tree.structure(now) == jax.tree.structure(before)
    for a, b in zip(jax.tree.leaves(now), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


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
