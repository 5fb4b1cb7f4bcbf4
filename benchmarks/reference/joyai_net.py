"""JoyAI-LLM-Flash's decoder with its next-next-token module, plain:
one chip's share of sixteen.

Written from the ``joyai_llm_flash`` ``config.json`` (jdopensource/
JoyAI-LLM-Flash; the layer equations are the DeepSeek-V3 family's) in
straightforward ``jax.numpy``, float32 at matmul precision ``highest``,
one sequence at a time: every head's keys and values made from the
latent, every query block against every key, every held expert over
every position.  What does not differ from ``trinity_net`` (the
projections' ``dot``, the norm, the SwiGLU, the routed experts with
their share) is imported from it; nothing of the program is.

    h = E[tokens]                                (no embedding scale)
    per layer:
      x  = RMSNorm(h)
      cq = RMSNorm(x Wqa);  q = cq Wqb           q = [q_nope ; q_rope] a head
      [ckv ; kr] = x Wkva;  ckv = RMSNorm(ckv)   ONE rotary key for all heads
      [k_nope ; v] = ckv Wkvb                    a head
      q_rope, kr: pairs (2i, 2i+1) turned by pos * theta^(-2i/rope)
                                                 (rope_interleave: true)
      score(t, s) = (q_nope_t . k_nope_s + q_rope_t . kr_s)
                    / sqrt(nope + rope),  s <= t
                       ASSUMED (scale from qk_head_dim; rope_scaling null)
      p = softmax over visible s;  o = p v
      h = h + concat(o) Wo                       no gate, no norm after
      m = RMSNorm(h)
      layers < first_k_dense_replace: f = (silu(m W1) * (m W3)) W2
      the others: s = sigmoid(m Wr) in float32; the num_experts_per_tok
        largest of s + b are selected
                       ASSUMED (b, the noaux_tc selection bias, is a zero
                       buffer; n_group = topk_group = 1: no grouping; no
                       auxiliary balance term)
        w_e = routed_scaling_factor * s_e / sum over the selected of s
        f = sum over selected e HELD HERE of w_e * SwiGLU_e(m)
            + SwiGLU_shared(m)
      h = h + f
    z = RMSNorm(h_L); policy = z Wh (untied); value = tanh(z wv)  DEPARTURE
    the module (num_nextn_predict_layers 1), at position t:
      u_t = [RMSNorm(h_L,t) ; RMSNorm(E[token_{t+1}])] Weh
      u -> one decoder layer of the expert form -> RMSNorm -> Wh
                       ASSUMED (the family's form, and that its layer is
                       an expert layer; the config gives the count alone)
      E and Wh are the model's own, shared; the gradient is not stopped
      at h_L           ASSUMED

The share: experts ``held_first .. held_first + E_held - 1`` of the
router's 256 are held (``E_held`` is the stack the weights come with),
what the others would have added is left out, and the weights ``w_e``
are normalised over all the selected, held or not; the vocabulary is
the slice the weights come with.  DEPARTURE: the value head,
``tanh(z wv)``, is the RL value and no part of the language model.

What the weights' shapes do not say is in ``GEOMETRY``; the tests put
their tiny preset's there.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from .layers import rounded
from .trinity_net import dot, experts, rms_norm, swiglu

RECURRENT = False

GEOMETRY = {
    # published layers 0 (dense), 1-4 (experts), and the module
    "num_hidden_layers": 5,
    "first_k_dense_replace": 1,
    "num_attention_heads": 32,
    "num_experts_per_tok": 8,
    "route_scale": 2.5,             # routed_scaling_factor
    "held_first": 0,
    "rope_theta": 32e6,
    "rms_norm_eps": 1e-6,
    "query_block": 512,
}


def rope_interleaved(x, theta):
    """x (T, H, D): turn pairs (2i, 2i + 1) by position * theta^(-2i/D)."""
    T, _, D = x.shape
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def attention(x, p, lowp, g):
    """x (T, d) -> (T, d): latent attention with every head's keys and
    values multiplied out; the scores a block of queries at a time,
    each block against EVERY key with what it may not see masked, and
    made again coming back."""
    T = x.shape[0]
    H, eps = g["num_attention_heads"], g["rms_norm_eps"]
    rank = p["kv_norm"]["scale"].shape[0]
    rope = p["kv_a"]["kernel"].shape[1] - rank
    cq = rms_norm(dot(x, p["q_a"]["kernel"], lowp), p["q_norm"], lowp, eps)
    q = dot(cq, p["q_b"]["kernel"], lowp).reshape(T, H, -1)
    nope = q.shape[-1] - rope
    kv_a = dot(x, p["kv_a"]["kernel"], lowp)
    ckv = rms_norm(kv_a[:, :rank], p["kv_norm"], lowp, eps)
    kv = dot(ckv, p["kv_b"]["kernel"], lowp).reshape(T, H, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rounded(rope_interleaved(q[..., nope:], g["rope_theta"]), lowp)
    kr = rounded(rope_interleaved(kv_a[:, None, rank:], g["rope_theta"]),
                 lowp)                               # (T, 1, rope)
    q_nope = q[..., :nope]
    block = g["query_block"] if T % g["query_block"] == 0 else T
    s = jnp.arange(T)[None]

    @jax.checkpoint
    def attend(lo):
        take = lambda a: lax.dynamic_slice_in_dim(a, lo, block)  # noqa: E731
        scores = (
            jnp.einsum("thd,shd->hts", take(q_nope), k_nope,
                       precision=lax.Precision.HIGHEST)
            + jnp.einsum("thr,sr->hts", take(q_rope), kr[:, 0],
                         precision=lax.Precision.HIGHEST)
        ) / math.sqrt(nope + rope)
        seen = s <= lo + jnp.arange(block)[:, None]
        prob = rounded(jax.nn.softmax(
            jnp.where(seen[None], scores, -jnp.inf), -1), lowp)
        return jnp.einsum("hts,shd->thd", prob, v,
                          precision=lax.Precision.HIGHEST)

    o = rounded(lax.map(attend, jnp.arange(0, T, block)).reshape(T, -1),
                lowp)
    return dot(o, p["o"]["kernel"], lowp)


def layer(h, p, dense, lowp, g):
    eps = g["rms_norm_eps"]
    h = h + attention(rms_norm(h, p["pre_attn_norm"], lowp, eps),
                      p["attn"], lowp, g)
    m = rms_norm(h, p["pre_mlp_norm"], lowp, eps)
    if dense:
        f = swiglu(m, p["mlp"]["w1"]["kernel"], p["mlp"]["w3"]["kernel"],
                   p["mlp"]["w2"]["kernel"], lowp)
    else:
        f = experts(m, p["moe"], lowp, g)
    return rounded(h + f, lowp)


def _layer(h, p, dense, lowp, g):
    # a layer's activations are made again coming back: one layer's,
    # not six layers', lie beside the weights
    return jax.checkpoint(
        lambda h, p: layer(h, p, dense, lowp, g))(h, p)


def sequence(params, tokens, lowp=None, geometry=None):
    """One sequence ``tokens (T,)`` -> logits ``(T, vocab)``, value
    ``(T, 1)`` and the module's logits ``(T, vocab)``: its prediction
    at ``t`` is of the token at ``t + 2``, and its last position (which
    no token follows) is given the first token, reaching no term."""
    g = geometry or GEOMETRY
    eps = g["rms_norm_eps"]
    table, head = params["embedding"], params["head"]["kernel"]
    h = rounded(table[tokens], lowp)
    for i in range(g["num_hidden_layers"]):
        h = _layer(h, params[f"layer_{i}"],
                   i < g["first_k_dense_replace"], lowp, g)
    z = rms_norm(h, params["final_norm"], lowp, eps)
    p = params["mtp"]
    following = rounded(table[jnp.roll(tokens, -1)], lowp)
    u = dot(jnp.concatenate([rms_norm(h, p["state_norm"], lowp, eps),
                             rms_norm(following, p["token_norm"], lowp, eps)],
                            -1), p["join"]["kernel"], lowp)
    u = _layer(u, p["layer"], False, lowp, g)
    return {"policy": dot(z, head, lowp),
            "value": jnp.tanh(dot(z, params["value_head"]["kernel"], lowp)),
            "mtp": dot(rms_norm(u, p["final_norm"], lowp, eps), head, lowp)}


def forward(params, obs, hidden=None, lowp=None):
    """obs (N, T) tokens -> the heads ``(N, T, ...)``, a sequence at a
    time."""
    rows = [sequence(params, tokens, lowp) for tokens in obs]
    return {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
