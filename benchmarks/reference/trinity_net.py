"""Trinity-Mini's decoder, plain: one chip's share of eight.

Written from the ``afmoe`` ``config.json`` (arcee-ai/Trinity-Mini) in
straightforward ``jax.numpy``, float32 at matmul precision ``highest``,
one sequence at a time: every query block against every key where the
program skips what a window hides, every held expert over every
position where the program sorts its picks.  It imports nothing of the
program.

    h = E[tokens] * sqrt(hidden)                  ASSUMED (mup_enabled)
    per layer l of type layer_types[l]:
      a = RMSNorm(h); q, k, v = a Wq, a Wk, a Wv  (32 / 4 / 4 heads of 128)
      q, k = RMSNorm_head(q), RMSNorm_head(k)     ASSUMED (per head)
      sliding_attention: RoPE(theta) on q, k; key s visible to t iff
        0 <= t - s < sliding_window
      full_attention: s <= t, no rotation         ASSUMED (no rotation)
      p = softmax(q.k / sqrt(128)) over visible s; each key-value head
        serves heads / kv_heads query heads; o = p v
      o = o * sigmoid(a Wg)                       ASSUMED (output gate)
      h = h + RMSNorm(o Wo)                       ASSUMED (post norm)
      m = RMSNorm(h)
      dense layers: f = (silu(m W1) * (m W3)) W2
      expert layers: s = sigmoid(m Wr) in float32; the experts_per_tok
        largest are selected (the selection bias is a zero buffer; one
        group); w_e = route_scale * s_e / sum over the selected of s;
        f = sum over selected e HELD HERE of w_e * SwiGLU_e(m)
            + SwiGLU_shared(m)
      h = h + RMSNorm(f)                          ASSUMED (post norm)
    z = RMSNorm(h); policy = z Wh (untied); value = tanh(z wv)  DEPARTURE

The share: experts ``held_first .. held_first + E_held - 1`` of the
router's 128 are held (``E_held`` is the stack the weights come with),
what the others would have added is left out, and the weights ``w_e``
are normalised over all the selected, held or not; the vocabulary is
the slice the weights come with.  ``load_balance_coeff`` names a
coefficient and no formula: no auxiliary term.  DEPARTURE: the value
head, ``tanh(z wv)``, is the RL value and no part of the language model.

What the weights' shapes do not say is in ``GEOMETRY``; the tests put
their tiny preset's there.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from .layers import rounded

RECURRENT = False
SLIDING, FULL = "sliding_attention", "full_attention"

GEOMETRY = {
    # published layers 0 (dense), 2, 3, 4, 5
    "layer_types": (SLIDING, SLIDING, FULL, SLIDING, SLIDING),
    "num_dense_layers": 1,
    "sliding_window": 2048,
    "num_experts_per_tok": 8,
    "route_scale": 2.826,
    "held_first": 0,
    "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5,
    "query_block": 512,
}


def dot(x, w, lowp):
    return rounded(jnp.dot(rounded(x, lowp), rounded(w, lowp),
                           precision=lax.Precision.HIGHEST), lowp)


def rms_norm(x, p, lowp, eps):
    y = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return rounded(y * p["scale"], lowp)


def rope(x, theta):
    """x (T, H, D): rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(a, p, kind, lowp, g):
    """a (T, d) -> (T, d): the scores a block of queries at a time,
    each block against EVERY key with what it may not see masked (no
    block is told what to skip), and made again coming back, so that
    the whole window fits beside the weights."""
    T = a.shape[0]
    D = p["q_norm"]["scale"].shape[0]
    eps = g["rms_norm_eps"]
    q = dot(a, p["q"]["kernel"], lowp).reshape(T, -1, D)
    k = dot(a, p["k"]["kernel"], lowp).reshape(T, -1, D)
    v = dot(a, p["v"]["kernel"], lowp).reshape(T, -1, D)
    q = rms_norm(q, p["q_norm"], lowp, eps)
    k = rms_norm(k, p["k_norm"], lowp, eps)
    if kind == SLIDING:
        q = rounded(rope(q, g["rope_theta"]), lowp)
        k = rounded(rope(k, g["rope_theta"]), lowp)
    heads, kv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, heads // kv, axis=1)      # each kv head serves a group
    v = jnp.repeat(v, heads // kv, axis=1)
    block = g["query_block"] if T % g["query_block"] == 0 else T
    s = jnp.arange(T)[None]

    @jax.checkpoint
    def attend(lo):
        scores = jnp.einsum(
            "thd,shd->hts", lax.dynamic_slice_in_dim(q, lo, block), k,
            precision=lax.Precision.HIGHEST) / math.sqrt(D)
        t = lo + jnp.arange(block)[:, None]
        seen = s <= t
        if kind == SLIDING:
            seen = seen & (t - s < g["sliding_window"])
        prob = rounded(jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), -1), lowp)
        return jnp.einsum("hts,shd->thd", prob, v,
                          precision=lax.Precision.HIGHEST)

    o = rounded(lax.map(attend, jnp.arange(0, T, block)).reshape(T, -1),
                lowp)
    o = rounded(o * jax.nn.sigmoid(dot(a, p["gate"]["kernel"], lowp)), lowp)
    return dot(o, p["o"]["kernel"], lowp)


def swiglu(m, w1, w3, w2, lowp):
    return dot(rounded(jax.nn.silu(dot(m, w1, lowp)) * dot(m, w3, lowp),
                       lowp), w2, lowp)


def stacked(spec, x, w, lowp):
    """``dot`` over a stack of independent kernels (the held experts)."""
    return rounded(jnp.einsum(spec, rounded(x, lowp), rounded(w, lowp),
                              precision=lax.Precision.HIGHEST), lowp)


def experts(m, p, lowp, g):
    """m (T, d): every held expert over every position, weighted by the
    router's weight where it was selected and by nought where not."""
    scores = jax.nn.sigmoid(jnp.dot(m, p["router"]["kernel"],
                                    precision=lax.Precision.HIGHEST))
    top, chosen = lax.top_k(scores, g["num_experts_per_tok"])
    weights = g["route_scale"] * top / top.sum(-1, keepdims=True)
    w1, w3, w2 = (p["experts"][k]["kernel"] for k in ("w1", "w3", "w2"))
    held = g["held_first"] + jnp.arange(w1.shape[0])
    # (T, E): the weight of each held expert at each position
    w_e = jnp.where(chosen[..., None] == held, weights[..., None], 0.0).sum(1)
    hidden = rounded(jax.nn.silu(stacked("td,edf->etf", m, w1, lowp))
                     * stacked("td,edf->etf", m, w3, lowp), lowp)
    each = stacked("etf,efd->etd", hidden, w2, lowp)
    y = swiglu(m, p["shared"]["w1"]["kernel"], p["shared"]["w3"]["kernel"],
               p["shared"]["w2"]["kernel"], lowp)
    return rounded(y + jnp.einsum("te,etd->td", w_e, each,
                                  precision=lax.Precision.HIGHEST), lowp)


def layer(h, p, kind, dense, lowp, g):
    eps = g["rms_norm_eps"]
    a = rms_norm(h, p["pre_attn_norm"], lowp, eps)
    h = h + rms_norm(attention(a, p["attn"], kind, lowp, g),
                     p["post_attn_norm"], lowp, eps)
    m = rms_norm(h, p["pre_mlp_norm"], lowp, eps)
    if dense:
        f = swiglu(m, p["mlp"]["w1"]["kernel"], p["mlp"]["w3"]["kernel"],
                   p["mlp"]["w2"]["kernel"], lowp)
    else:
        f = experts(m, p["moe"], lowp, g)
    return rounded(h + rms_norm(f, p["post_mlp_norm"], lowp, eps), lowp)


def sequence(params, tokens, lowp=None, geometry=None):
    """One sequence ``tokens (T,)`` -> logits ``(T, vocab)``, value
    ``(T, 1)``."""
    g = geometry or GEOMETRY
    d = params["embedding"].shape[1]
    h = rounded(params["embedding"][tokens] * math.sqrt(d), lowp)
    for i, kind in enumerate(g["layer_types"]):
        # a layer's activations are made again coming back: one
        # layer's, not five layers', lie beside the weights
        h = jax.checkpoint(
            lambda h, p, kind=kind, dense=i < g["num_dense_layers"]:
            layer(h, p, kind, dense, lowp, g))(h, params[f"layer_{i}"])
    z = rms_norm(h, params["final_norm"], lowp, g["rms_norm_eps"])
    return {"policy": dot(z, params["head"]["kernel"], lowp),
            "value": jnp.tanh(dot(z, params["value_head"]["kernel"], lowp))}


def forward(params, obs, hidden=None, lowp=None):
    """obs (N, T) tokens -> {"policy": (N, T, vocab), "value": (N, T, 1)},
    a sequence at a time."""
    rows = [sequence(params, tokens, lowp) for tokens in obs]
    return {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
