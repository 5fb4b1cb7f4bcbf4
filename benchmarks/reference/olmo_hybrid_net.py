"""Olmo-Hybrid-7B's decoder, plain: one chip's share of two.

Written from the ``olmo_hybrid`` ``config.json`` (allenai/Olmo-Hybrid-7B)
in straightforward ``jax.numpy``, float32 at matmul precision
``highest``, one sequence at a time, the recurrence ONE POSITION at a
time exactly as it is written below (the program runs it chunk-wise).
What does not differ from ``trinity_net`` (the projections' ``dot``,
the norm, the SwiGLU) is imported from it; nothing of the program is.

    h = E[tokens]                                 (no embedding scale)
    per layer l of type layer_types[l], x = h     ASSUMED (no norm going IN)
      linear_attention (Gated DeltaNet, arXiv:2412.06464; H heads held,
      dk = linear_key_head_dim, dv = linear_value_head_dim):
        q~ = x Wq (H*dk)   k~ = x Wk (H*dk)   v~ = x Wv (H*dv)   no bias
        q^, k^, v^ = SiLU(causal depthwise conv over time, conv_kernel
          taps: y_t = sum_j w[j] x_{t - taps + 1 + j}, zeros before the
          episode's first position; one filter a channel, no bias)
                                                  ASSUMED (no bias, SiLU)
        per head: q = l2norm(q^) / sqrt(dk);  k = l2norm(k^);  v = v^
          l2norm(u) = u / sqrt(sum u^2 + 1e-6)    ASSUMED (scale, eps)
        beta_t = 2 * sigmoid(x Wb)                in (0, 2): allow_neg_eigval
        g_t = -exp(A_log) * softplus(x Wa + dt_bias);  alpha_t = exp(g_t)
        S_t = alpha_t * S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
              S in R^{dv x dk} a head, S_0 = 0
        o_t = S_t q_t
        y_t = concat_h(RMSNorm_dv(o_t,h) * SiLU((x Wg)_h)) Wo
                                                  ASSUMED (gated norm: one
                                                  gain of dv for all heads,
                                                  eps = rms_norm_eps)
      full_attention: q, k, v = x Wq, x Wk, x Wv  (heads of 128, no bias)
        q, k = RMSNorm(q), RMSNorm(k) over the WHOLE projection, with a
          gain, before the split into heads       ASSUMED (family: OLMo 2)
        no rotation                               ASSUMED (rope_theta: null)
        p = softmax(q.k / sqrt(128)) over s <= t; o = p v; y = concat(o) Wo
      h = h + RMSNorm(y)                          ASSUMED (family: the norm
      h = h + RMSNorm((silu(h W1) * (h W3)) W2)   comes after a branch)
    z = RMSNorm(h); policy = z Wh (untied); value = tanh(z wv)  DEPARTURE

The share: each mixer holds the heads its weights come with (heads
``first .. first + H - 1`` of the published 30; ``Wo`` has rows for
those alone); what the other heads would add to ``y`` is left out.  The
one quantity that is not a head's own, the mean square under the
attention's q/k norm, is taken over the columns held.  The vocabulary
is the slice the weights come with.  DEPARTURE: the value head,
``tanh(z wv)``, is the RL value and no part of the language model.

The gradient of 4,096 positions: the scan over positions is cut into
blocks of ``scan_block``, each made again coming back
(``jax.checkpoint``), so that one state a block is kept and not one a
position (15 x 192 x 96 float32 x 4,096 = 4.5 GB a layer otherwise).

What the weights' shapes do not say is in ``GEOMETRY``; the tests put
their tiny preset's there.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from .layers import rounded
from .trinity_net import dot, rms_norm, swiglu

RECURRENT = False
LINEAR, FULL = "linear_attention", "full_attention"

GEOMETRY = {
    # one period of the published pattern
    "layer_types": (LINEAR, LINEAR, LINEAR, FULL),
    "attention_head_dim": 128,
    "allow_neg_eigval": True,
    "rms_norm_eps": 1e-6,
    "l2_norm_eps": 1e-6,
    "query_block": 512,
    "scan_block": 64,
}


def short_conv(x, kernel):
    """``x (T, C)``, ``kernel (taps, C)``: ``y_t = sum_j kernel[j] *
    x_{t - taps + 1 + j}``, zeros before position 0."""
    taps, T = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1])), x])
    return sum(kernel[j] * padded[j:j + T] for j in range(taps))


def recurrence(q, k, v, g, beta, block):
    """The gated delta rule, a position at a time: ``q, k (T, H, dk)``,
    ``v (T, H, dv)``, ``g, beta (T, H)`` -> ``o (T, H, dv)``."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    block = min(block, T)
    pad = -T % block      # zeros behind the last position move nothing

    def blocks(x):
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((-1, block) + x.shape[1:])

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        alpha, b_t = jnp.exp(g_t)[:, None, None], b_t[:, None, None]
        Sk = jnp.einsum("hvk,hk->hv", S, k_t,
                        precision=lax.Precision.HIGHEST)
        S = alpha * (S - b_t * Sk[:, :, None] * k_t[:, None, :]) \
            + b_t * v_t[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q_t,
                             precision=lax.Precision.HIGHEST)

    @jax.checkpoint
    def run(S, xs):
        return lax.scan(step, S, xs)

    _, o = lax.scan(run, jnp.zeros((H, dv, dk)),
                    tuple(blocks(x) for x in (q, k, v, g, beta)))
    return o.reshape(-1, H, dv)[:T]


def delta_mixer(x, p, lowp, g):
    """x (T, d) -> (T, d)."""
    T = x.shape[0]
    H = p["A_log"].shape[0]

    def conved(name):
        y = short_conv(dot(x, p[name]["kernel"], lowp),
                       p[name + "_conv"]["kernel"])
        return rounded(jax.nn.silu(y), lowp).reshape(T, H, -1)

    def l2norm(u):
        return u / jnp.sqrt((u * u).sum(-1, keepdims=True)
                            + g["l2_norm_eps"])

    q, k, v = conved("q"), conved("k"), conved("v")
    q = rounded(l2norm(q) / math.sqrt(q.shape[-1]), lowp)
    k = rounded(l2norm(k), lowp)
    beta = jax.nn.sigmoid(dot(x, p["b"]["kernel"], lowp))
    if g["allow_neg_eigval"]:
        beta = 2.0 * beta
    decay = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        dot(x, p["a"]["kernel"], lowp) + p["dt_bias"])
    o = rounded(recurrence(q, k, v, decay, rounded(beta, lowp),
                           g["scan_block"]), lowp)
    gate = rounded(jax.nn.silu(dot(x, p["g"]["kernel"], lowp)), lowp)
    o = rms_norm(o, p["o_norm"], lowp, g["rms_norm_eps"])
    o = rounded(o * gate.reshape(o.shape), lowp)
    return dot(o.reshape(T, -1), p["o"]["kernel"], lowp)


def attention(x, p, lowp, g):
    """x (T, d) -> (T, d): full causal attention, every head its own
    keys and values, the scores a block of queries at a time against
    EVERY key and made again coming back."""
    T, D, eps = x.shape[0], g["attention_head_dim"], g["rms_norm_eps"]
    q = rms_norm(dot(x, p["q"]["kernel"], lowp), p["q_norm"], lowp, eps)
    k = rms_norm(dot(x, p["k"]["kernel"], lowp), p["k_norm"], lowp, eps)
    q, k = q.reshape(T, -1, D), k.reshape(T, -1, D)
    v = dot(x, p["v"]["kernel"], lowp).reshape(T, -1, D)
    block = g["query_block"] if T % g["query_block"] == 0 else T
    s = jnp.arange(T)[None]

    @jax.checkpoint
    def attend(lo):
        scores = jnp.einsum(
            "thd,shd->hts", lax.dynamic_slice_in_dim(q, lo, block), k,
            precision=lax.Precision.HIGHEST) / math.sqrt(D)
        seen = s <= lo + jnp.arange(block)[:, None]
        prob = rounded(jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), -1), lowp)
        return jnp.einsum("hts,shd->thd", prob, v,
                          precision=lax.Precision.HIGHEST)

    o = rounded(lax.map(attend, jnp.arange(0, T, block)).reshape(T, -1),
                lowp)
    return dot(o, p["o"]["kernel"], lowp)


def layer(h, p, kind, lowp, g):
    eps = g["rms_norm_eps"]
    y = delta_mixer(h, p["delta"], lowp, g) if kind == LINEAR \
        else attention(h, p["attn"], lowp, g)
    h = h + rms_norm(y, p["post_attn_norm"], lowp, eps)
    f = swiglu(h, p["mlp"]["w1"]["kernel"], p["mlp"]["w3"]["kernel"],
               p["mlp"]["w2"]["kernel"], lowp)
    return rounded(h + rms_norm(f, p["post_mlp_norm"], lowp, eps), lowp)


def sequence(params, tokens, lowp=None, geometry=None):
    """One sequence ``tokens (T,)`` -> logits ``(T, vocab)``, value
    ``(T, 1)``."""
    g = geometry or GEOMETRY
    h = rounded(params["embedding"][tokens], lowp)
    for i, kind in enumerate(g["layer_types"]):
        # a layer's activations are made again coming back
        h = jax.checkpoint(
            lambda h, p, kind=kind: layer(h, p, kind, lowp, g))(
                h, params[f"layer_{i}"])
    z = rms_norm(h, params["final_norm"], lowp, g["rms_norm_eps"])
    return {"policy": dot(z, params["head"]["kernel"], lowp),
            "value": jnp.tanh(dot(z, params["value_head"]["kernel"], lowp))}


def forward(params, obs, hidden=None, lowp=None):
    """obs (N, T) tokens -> {"policy": (N, T, vocab), "value": (N, T, 1)},
    a sequence at a time."""
    rows = [sequence(params, tokens, lowp) for tokens in obs]
    return {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}

