"""GeisterNet, plain: scalar features broadcast onto the 6x6 board, a
conv stem, a 3-layer DRC body (ConvLSTM cells, arXiv:1901.03559)
repeated 3 times a step, a move head (4 directions x 36 cells), a
70-way set head from the turn-colour scalar, a tanh value head and an
unsquashed return head.  GroupNorm in BatchNorm's place, as the repo's
models state."""

import jax
import jax.numpy as jnp

from .layers import conv, dense, group_norm, leaky_relu, rounded

RECURRENT = True
BOARD = (6, 6)
LAYERS = 3
REPEATS = 3
FILTERS = 32


def init_hidden(batch_shape):
    shape = tuple(batch_shape) + BOARD + (FILTERS,)
    return {f"{k}{i}": jnp.zeros(shape, jnp.float32)
            for i in range(LAYERS) for k in ("h", "c")}


def _cell(x, h, c, p, lowp):
    gates = conv(jnp.concatenate([x, h], -1), p["Conv_0"], "SAME", lowp)
    i, f, o, g = jnp.split(gates, 4, axis=-1)
    c = rounded(jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g),
                lowp)
    return rounded(jax.nn.sigmoid(o) * jnp.tanh(c), lowp), c


def _value_head(x, p, lowp, squash):
    h = rounded(leaky_relu(conv(x, p["Conv_0"], "SAME", lowp)), lowp)
    h = dense(h.reshape(h.shape[0], -1), p["Dense_0"], lowp)
    return jnp.tanh(h) if squash else h


def forward(params, obs, hidden, lowp=None):
    board, scalar = obs["board"], obs["scalar"]
    n = board.shape[0]
    planes = jnp.broadcast_to(scalar[:, None, None, :],
                              (n,) + BOARD + (scalar.shape[-1],))
    h = jnp.concatenate([planes, board], -1)
    h = jnp.maximum(group_norm(conv(h, params["Conv_0"], "SAME", lowp),
                               params["GroupNorm_0"], lowp), 0)
    hs = [hidden[f"h{i}"] for i in range(LAYERS)]
    cs = [hidden[f"c{i}"] for i in range(LAYERS)]
    for _ in range(REPEATS):
        for i in range(LAYERS):
            inp = hs[i - 1] if i > 0 else h
            hs[i], cs[i] = _cell(
                inp, hs[i], cs[i],
                params["DRC_0"][f"ConvLSTMCell_{i}"], lowp)
    body = hs[-1]
    pm = jnp.maximum(group_norm(conv(body, params["Conv_1"], "SAME", lowp),
                                params["GroupNorm_1"], lowp), 0)
    pm = conv(pm, params["Conv_2"], "SAME", lowp)          # (N, 6, 6, 4)
    pm = jnp.transpose(pm, (0, 3, 1, 2)).reshape(n, -1)    # d*36 + x*6 + y
    ps = dense(scalar[:, :1], params["Dense_0"], lowp)
    new_hidden = {}
    for i in range(LAYERS):
        new_hidden[f"h{i}"], new_hidden[f"c{i}"] = hs[i], cs[i]
    return {"policy": jnp.concatenate([pm, ps], -1),
            "value": _value_head(body, params["ValueHead_0"], lowp, True),
            "return": _value_head(body, params["ValueHead_1"], lowp, False),
            "hidden": new_hidden}
