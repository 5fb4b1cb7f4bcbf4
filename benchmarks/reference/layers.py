"""Layer primitives of the plain reference: straightforward
``jax.numpy`` in float32 at matmul precision ``highest``.

``lowp`` names the control's precision (see PERF.md, "how correct is
decided"): with ``"fp8"`` every tensor a low-precision pipeline would
hold in that type -- the operands of each conv/dense and the result of
each conv, dense, norm and activation -- is rounded to float8_e4m3fn
(the step below the configurations' bfloat16), with ``"bf16"`` to
bfloat16; the arithmetic between two roundings stays float32.
"""

import jax
import jax.numpy as jnp
from jax import lax

_LOWP = {None: None, "bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def rounded(x, lowp):
    dtype = _LOWP[lowp]
    if dtype is None:
        return x
    return x.astype(dtype).astype(jnp.float32)


def conv(x, p, padding, lowp):
    """NHWC conv with an HWIO kernel (the layout of the weights file)."""
    y = lax.conv_general_dilated(
        rounded(x, lowp), rounded(p["kernel"], lowp), (1, 1), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    if "bias" in p:
        y = y + p["bias"]
    return rounded(y, lowp)


def dense(x, p, lowp):
    y = jnp.dot(rounded(x, lowp), rounded(p["kernel"], lowp),
                precision=lax.Precision.HIGHEST)
    if "bias" in p:
        y = y + p["bias"]
    return rounded(y, lowp)


def num_groups(channels, target=8):
    return max(g for g in range(1, min(target, channels) + 1)
               if channels % g == 0)


def group_norm(x, p, lowp=None, eps=1e-6):
    """GroupNorm over (H, W, C/groups) per sample, then scale + bias."""
    n, h, w, c = x.shape
    g = num_groups(c)
    xg = x.reshape(n, h, w, g, c // g)
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return rounded(xg.reshape(n, h, w, c) * p["scale"] + p["bias"], lowp)


def leaky_relu(x, slope=0.1):
    return jnp.where(x >= 0, x, slope * x)
