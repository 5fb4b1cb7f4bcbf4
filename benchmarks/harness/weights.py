"""Initial weights, made by the benchmark from ``--seed`` on the device
in one jitted call: the program's module gives only the SHAPES (an
abstract ``init``); every value is drawn here, so the plain reference
is handed nothing the program computed.

A leaf's scale follows from its name and shape, and from two lists a
configuration may state: ``head_layers`` (conv layers that emit a
head's output) and ``stacked_layers`` (layers whose ``kernel`` is a
stack of independent kernels along its leading axis)."""

import math

import jax
import jax.numpy as jnp


def param_shapes(module, example_obs, hidden):
    obs_b = jax.tree.map(lambda a: jnp.asarray(a)[None], example_obs)
    return jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), obs_b, hidden)
    )["params"]


def make_params(shapes, seed, head_layers=(), stacked_layers=()):
    """kernels ~ N(0, 1/fan_in), fan-in being every axis but the last;
    under a layer the configuration names in ``stacked_layers`` a kernel's
    leading axis is a stack of independent kernels (experts) and stays
    out of the fan-in.  But the layers that emit a head's
    output (every dense layer of these nets, and the conv layers the
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
