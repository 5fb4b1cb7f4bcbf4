"""Initial weights, made by the benchmark from ``--seed`` on the device
in one jitted call: the program's module gives only the SHAPES (an
abstract ``init``); every value is drawn here, so the plain reference
is handed nothing the program computed.

A leaf's scale follows from its name and shape, and from three lists a
configuration may state: ``head_layers`` (conv layers that emit a
head's output), ``stacked_layers`` (layers whose ``kernel`` is a
stack of independent kernels along its leading axis) and
``trunk_layers`` (layers whose 2-D kernels are projections of the
trunk, not heads); naming a parent module covers what lies under it."""

import math

import jax
import jax.numpy as jnp


def param_shapes(module, example_obs, hidden):
    obs_b = jax.tree.map(lambda a: jnp.asarray(a)[None], example_obs)
    return jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), obs_b, hidden)
    )["params"]


LAYER_LISTS = ("head_layers", "stacked_layers", "trunk_layers")


def config_params(shapes, seed, config):
    """``make_params`` with the lists the configuration states."""
    return make_params(shapes, seed,
                       *(config.get(key, ()) for key in LAYER_LISTS))


def make_params(shapes, seed, head_layers=(), stacked_layers=(),
                trunk_layers=()):
    """kernels ~ N(0, 1/fan_in), fan-in being every axis but the last;
    under a layer the configuration names in ``stacked_layers`` a kernel's
    leading axis is a stack of independent kernels (experts) and stays
    out of the fan-in.  But the layers that emit a head's
    output (every dense layer, but those under a layer the configuration
    names in ``trunk_layers``: in a transformer every projection is a
    2-D kernel, and drawn as heads they would leave the residual stream
    to the embedding alone; and the conv layers the
    configuration names in ``head_layers``) ~ N(0, 0.01/fan_in), so that
    policies start near uniform and values unsaturated, as heads are
    commonly initialised: with unit-variance heads the softmax
    saturates, a few samples carry the whole gradient, and the output
    check's numbers swing fivefold from seed to seed (my chip runs,
    PR 24); norm scales ~ 1 + 0.1 N and biases ~ 0.1 N (non-zero, so a
    dropped bias or scale shows in the output check)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, leaf) in enumerate(leaves):
            z = jax.random.normal(jax.random.fold_in(key, i), leaf.shape,
                                  jnp.float32)
            name = path[-1].key
            under = {getattr(k, "key", None) for k in path}
            if name == "kernel":
                stack = not under.isdisjoint(stacked_layers)
                z = z / math.sqrt(math.prod(leaf.shape[int(stack):-1]))
                head = (len(leaf.shape) == 2
                        and under.isdisjoint(trunk_layers))
                if head or not under.isdisjoint(head_layers):
                    z = 0.1 * z
            elif name == "scale":
                z = 1.0 + 0.1 * z
            else:
                z = 0.1 * z
            out.append(z)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(jax.random.PRNGKey(seed))
