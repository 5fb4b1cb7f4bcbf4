"""GeeseNet, plain: a 32-filter torus-conv stem and 12 residual torus
blocks on the 7x11 board, the policy read from the goose's head cell,
the value from [head features, board-average features].  Written from
the published HandyRL GeeseNet with GroupNorm in BatchNorm's place (the
departure this repo's models make, stated in its models/blocks.py)."""

import jax.numpy as jnp

from .layers import conv, dense, group_norm, rounded

RECURRENT = False


def torus_conv(x, p, lowp):
    h = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)), mode="wrap")
    return group_norm(conv(h, p["Conv_0"], "VALID", lowp),
                      p["GroupNorm_0"], lowp)


def forward(params, obs, hidden=None, lowp=None):
    """obs (N, 7, 11, 17) -> {"policy": (N, 4), "value": (N, 1)}."""
    blocks = sum(1 for k in params if k.startswith("TorusConv_")) - 1
    h = jnp.maximum(torus_conv(obs, params["TorusConv_0"], lowp), 0)
    for i in range(1, blocks + 1):
        h = rounded(jnp.maximum(
            h + torus_conv(h, params[f"TorusConv_{i}"], lowp), 0), lowp)
    head = rounded((h * obs[..., :1]).sum(axis=(1, 2)), lowp)
    avg = rounded(h.mean(axis=(1, 2)), lowp)
    policy = dense(head, params["Dense_0"], lowp)
    value = jnp.tanh(dense(jnp.concatenate([head, avg], -1),
                           params["Dense_1"], lowp))
    return {"policy": policy, "value": value}
