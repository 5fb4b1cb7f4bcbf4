"""A sparse-expert sequence policy: the training window IS the sequence.

A decoder over ``(B, T)`` tokens in one causal pass: embedding, per
layer a grouped-query attention (a sliding-window variant with rotary
positions and a full variant without) and a SwiGLU MLP (dense in the
leading layers, sparse experts after them), RMSNorm before and after
each, a policy head of vocabulary width and a tanh value head.  The
layer equations follow the ``afmoe`` family (Trinity-Mini's
``config.json``); what that file does not give is marked ASSUMED in
``benchmarks/configs/trinity_mini_ep8.yaml`` and written once more in
the plain reference, ``benchmarks/reference/trinity_net.py``.

The expert layer is TOLD which experts it holds (``first_expert``,
``experts_held``): it routes over all ``experts``, normalises the
weights over every selected expert, held or not, and computes its own
experts' part of the result for the positions routed to them -- the
chip's share under expert parallelism, on one chip without the
exchange.  No token is dropped: every held expert runs over every
position and is weighted by the router where it was selected; only a
window's padding past its episode's end (token -1) takes no expert.

Two call shapes, one set of parameters:

  * ``module(tokens (B, T), None)`` -- the learner's pass over whole
    windows.  Attention skips what causality and the window hide: on a
    TPU, at lane-wide shapes, as ONE fused kernel a layer whose scores
    never leave the chip's fast memory (``fused_attention``); everywhere
    else in query blocks of plain XLA (``blocked_attention``, the
    statement the kernel is held to).  Layers are rematerialised (all
    but the kernel's output); the policy comes back FACTORED
    (``ops.losses.FactoredPolicy``: trunk features and the head's
    kernel), so that the ``(B * T, vocab)`` logits never exist whole.
  * ``module(token (N,), hidden)`` -- the actor's one-token step through
    a key-value cache carried as the seat's ``hidden`` (``init_hidden``):
    dense logits for that position, the cache advanced by one.

``sequence_length`` (the cache's positions, the longest episode) is how
the module declares itself a sequence net: ``TPUModel.is_sequence``.
"""

import math
from functools import lru_cache, partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from ..ops.losses import FactoredPolicy

SLIDING, FULL = "sliding_attention", "full_attention"


class Sizes(NamedTuple):
    vocab: int
    hidden: int
    layer_types: Tuple[str, ...]   # attention type of each layer held
    dense_layers: int              # leading layers with a dense MLP
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    expert_width: int
    experts: int                   # the router's outputs
    experts_held: int
    first_expert: int
    experts_per_token: int
    shared_experts: int
    route_scale: float
    window: int
    sequence_length: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    attention_block: int = 512     # queries attended together (XLA path)


PRESETS = {
    # Trinity-Mini (arcee-ai, afmoe, 26B-A3B) at published widths: one
    # chip's share of eight -- experts 0-15 of 128, rows 0-25,023 of the
    # vocabulary, one leading dense layer and the period that follows
    "trinity_mini_ep8": Sizes(
        vocab=25024, hidden=2048,
        layer_types=(SLIDING, SLIDING, FULL, SLIDING, SLIDING),
        dense_layers=1, heads=32, kv_heads=4, head_dim=128,
        dense_width=6144, expert_width=1024, experts=128,
        experts_held=16, first_expert=0, experts_per_token=8,
        shared_experts=1, route_scale=2.826, window=2048,
        sequence_length=4096),
    # the same module at test size (tier-1, CPU)
    "tiny": Sizes(
        vocab=64, hidden=64, layer_types=(SLIDING, FULL, SLIDING),
        dense_layers=1, heads=4, kv_heads=2, head_dim=16,
        dense_width=128, expert_width=32, experts=8, experts_held=2,
        first_expert=0, experts_per_token=2, shared_experts=1,
        route_scale=2.826, window=8, sequence_length=32,
        attention_block=16),
}


def rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return rms_norm(x, scale, self.eps)


class Kernel(nn.Module):
    """A projection's kernel, handed out as it is; axes before the
    last two stack independent kernels (experts)."""
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1,
            batch_axis=tuple(range(len(self.shape) - 2)))
        return self.param("kernel", init, self.shape)


def _project(x, features, name):
    return jnp.dot(x, Kernel((x.shape[-1], features), name=name)())


def rotate(x, positions, theta):
    """Rotary positions on ``x (..., T, H, D)``, ``positions (..., T)``:
    the half-split convention, computed in float32."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions[..., None].astype(jnp.float32) * freq  # (..., T, D/2)
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[..., None, :]
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], -1)
    return (x32 * cos + half * sin).astype(x.dtype)


def _visible(t, s, window):
    """Key ``s`` is visible to query ``t``: causal, and within the
    window where the layer has one."""
    seen = s <= t
    return seen & (t - s < window) if window else seen


@partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _attend(q, k, v, q0, k0, window):
    """One block of queries ``q (B, Tq, KV, G, D)`` from position ``q0``
    against keys ``k, v (B, Tk, KV, D)`` from position ``k0``; the
    scores live in float32 and only inside this block (rematerialised
    coming back)."""
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    t = q0 + jnp.arange(q.shape[1])[:, None]
    s = k0 + jnp.arange(k.shape[1])[None]
    scores = jnp.where(_visible(t, s, window), scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", p, v)


def blocked_attention(q, k, v, window, block):
    """Causal attention over a whole window in blocks of queries, each
    against the keys it can see and no others: a window layer's block
    reads ``window + block`` keys at most, a full layer's every key up
    to its own end."""
    T = q.shape[1]
    out = []
    for q0 in range(0, T, block):
        q1 = min(q0 + block, T)
        # whole 128-lane tiles of keys; what the slice adds is masked
        k0 = max(0, q0 - window + 1) // 128 * 128 if window else 0
        out.append(_attend(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1],
                           q0, k0, window))
    return jnp.concatenate(out, axis=1)


LANES = 128                     # a TPU vector register's minor axis
# what a rematerialised layer keeps of its fused attention going
# forward: the kernel's output and one log-sum-exp a query (68 MB a
# layer at the published widths), so that the backward pass does not
# run the forward kernel again
KEPT = "fused_attention_out"
# the kernel's blocks, read on the chip at 4,096 positions, 4 x 8 heads
# of 128 (``PERF.md`` section 6, PR 34)
FUSED_BLOCK = 1024              # queries, and keys fetched, a grid step
FUSED_COMPUTE = 512             # keys a product inside one


@lru_cache(maxsize=None)
def _fused_kernel(T, window, groups, block, compute, interpret):
    """The library's fused attention (splash attention, multi-query
    form) for ONE key-value head's ``groups`` query heads over ``T``
    positions under the layer's own rule, ``_visible``: causal, and
    ``window - 1`` keys to the left of a query in a window layer; dq,
    dk and dv come from ONE backward kernel."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)

    mask = (masks.LocalMask((T, T), (window - 1, 0), 0) if window
            else masks.CausalMask((T, T)))
    blocks = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=compute,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)
    # the kernel's tables of which blocks to visit become arrays as it
    # is built: concrete ones, whatever trace asked first, since every
    # later trace is handed the same kernel
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            masks.MultiHeadMask([mask] * groups), block_sizes=blocks,
            residual_checkpoint_name=KEPT, interpret=interpret)


def _fused_blocks(T, D, block=None):
    """The kernel's ``(block, keys a product)`` over ``T`` positions of
    ``D``-wide heads (``block``: the module's constant unless given), or
    None where the shapes are not whole lanes and whole blocks."""
    block = block or min(FUSED_BLOCK, T)
    compute = min(FUSED_COMPUTE, block)
    if T % block or block % compute or compute % LANES or D % LANES:
        return None
    return block, compute


def fused_attention(q, k, v, window, block=None, interpret=False):
    """The same attention as ``blocked_attention`` over the same
    ``q (B, T, KV, G, D)`` and ``k, v (B, T, KV, D)``, as one kernel a
    layer: online softmax in float32 in the chip's fast memory, blocks
    of ``block`` x ``block`` that causality or the window hide whole
    skipped, the probabilities meeting ``v`` in ``v``'s dtype, and a
    backward kernel of its own that makes the scores again from q, k
    and one log-sum-exp a query.  No array of score size is written in
    either direction.  ``T`` is a multiple of ``block`` (the module's
    constant unless given), ``block`` and ``D`` multiples of 128;
    ``interpret`` runs the kernel's body as plain JAX (tier-1, on the
    CPU)."""
    B, T, KV, G, D = q.shape
    attend = _fused_kernel(T, window, G, *_fused_blocks(T, D, block),
                           interpret)
    # the kernel takes its scores unscaled
    q = (q * (1.0 / math.sqrt(D))).astype(q.dtype)
    o = jax.vmap(jax.vmap(attend))(          # over batch and kv head
        q.transpose(0, 2, 3, 1, 4), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3))             # (B, KV, G, T, D)
    return o.transpose(0, 3, 1, 2, 4)


def window_attention(q, k, v, window, block):
    """Attention over a whole window by the path the program can take
    where it is lowered: the fused kernel on a TPU when the shapes are
    whole lanes, query blocks of ``block`` in plain XLA everywhere else
    (a CPU, a head narrower than a lane).  Chosen per lowering
    platform, so a program compiled for a described chip from a CPU
    process takes the chip's path."""
    plain = partial(blocked_attention, window=window, block=block)
    if _fused_blocks(q.shape[1], q.shape[-1]) is None:
        return plain(q, k, v)
    return lax.platform_dependent(
        q, k, v, default=plain, tpu=partial(fused_attention, window=window))


class Attention(nn.Module):
    sizes: Sizes
    kind: str

    @nn.compact
    def __call__(self, a, cache=None, pos=None):
        z = self.sizes
        groups = z.heads // z.kv_heads
        window = z.window if self.kind == SLIDING else 0
        scope = "net.attention.window" if window else "net.attention.full"
        with jax.named_scope(scope):
            lead = a.shape[:-1]
            q = _project(a, z.heads * z.head_dim, "q").reshape(
                lead + (z.heads, z.head_dim))
            k = _project(a, z.kv_heads * z.head_dim, "k").reshape(
                lead + (z.kv_heads, z.head_dim))
            v = _project(a, z.kv_heads * z.head_dim, "v").reshape(
                lead + (z.kv_heads, z.head_dim))
            q = RMSNorm(z.eps, name="q_norm")(q)
            k = RMSNorm(z.eps, name="k_norm")(k)
            gate = jax.nn.sigmoid(
                _project(a, z.heads * z.head_dim, "gate"))
            if cache is None:
                # a whole window: (B, T, ...)
                B, T = lead
                if window:
                    positions = jnp.arange(T)[None]
                    q = rotate(q, positions, z.rope_theta)
                    k = rotate(k, positions, z.rope_theta)
                q = q.reshape(B, T, z.kv_heads, groups, z.head_dim)
                o = window_attention(q, k, v, window, z.attention_block)
                o = o.reshape(B, T, z.heads * z.head_dim)
            else:
                # one token a row, through the cache: (N, ...)
                if window:
                    q = rotate(q[:, None], pos[:, None], z.rope_theta)[:, 0]
                    k = rotate(k[:, None], pos[:, None], z.rope_theta)[:, 0]
                keys, values = cache                 # (N, S, KV, D)
                s = jnp.arange(keys.shape[1])
                here = (s[None] == pos[:, None])[..., None, None]
                keys = jnp.where(here, k[:, None].astype(keys.dtype), keys)
                values = jnp.where(
                    here, v[:, None].astype(values.dtype), values)
                cache = (keys, values)
                q = q.reshape(-1, z.kv_heads, groups, z.head_dim)
                scores = jnp.einsum(
                    "nkgd,nskd->nkgs", q.astype(jnp.float32),
                    keys.astype(jnp.float32)) / math.sqrt(z.head_dim)
                seen = _visible(pos[:, None], s[None], window)
                scores = jnp.where(seen[:, None, None], scores, -1e30)
                p = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("nkgs,nskd->nkgd", p,
                               values.astype(jnp.float32))
                o = o.reshape(-1, z.heads * z.head_dim).astype(a.dtype)
            o = _project(o * gate, z.hidden, "o")
        return o, cache


def swiglu(x, w1, w3, w2):
    return jnp.dot(jax.nn.silu(jnp.dot(x, w1)) * jnp.dot(x, w3), w2)


class SwiGLU(nn.Module):
    width: int

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        return swiglu(x, Kernel((d, self.width), name="w1")(),
                      Kernel((d, self.width), name="w3")(),
                      Kernel((self.width, d), name="w2")())


class Experts(nn.Module):
    """The held experts' SwiGLU kernels, stacked."""
    count: int
    width: int

    @nn.compact
    def __call__(self, d):
        return (Kernel((self.count, d, self.width), name="w1")(),
                Kernel((self.count, d, self.width), name="w3")(),
                Kernel((self.count, self.width, d), name="w2")())


def route(m, router, sizes):
    """Selected experts ``(N, k)`` and their weights ``(N, k)`` over ALL
    the router's experts: sigmoid scores in float32, the ``k`` largest,
    weights normalised over the selected and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        m.astype(jnp.float32), router.astype(jnp.float32)))
    # the selection bias of the family is a buffer that stays zero here
    top, selected = lax.top_k(scores, sizes.experts_per_token)
    weights = sizes.route_scale * top / top.sum(-1, keepdims=True)
    return selected, weights


def held_experts(m, selected, weights, kernels, sizes, valid=None):
    """What this chip's experts add: ``sum over selected e HELD HERE of
    w_e * SwiGLU_e(m)`` for ``m (N, d)``, and the positions routed to
    each held expert ``(experts_held,)``.  Rows that are not ``valid
    (N,)`` (padding past an episode's end, which reaches no loss term
    and no real position) take no expert.

    Every held expert runs over every position, its part weighted by
    the router's weight where it was selected and by nought where not:
    the held stack as ONE SwiGLU of width ``held * expert_width``, three
    dense products.  At sixteen held experts of 1,024 that is sixteen
    times the arithmetic the picks need (an eighth of all fall here by
    expectation), and the chip still does it sooner and, above all,
    in the same time whatever the picks were: sorted picks through
    ``lax.ragged_dot`` with a gather before and a scatter-add after
    cost 180-234 ms a step by the seed's router, row by row
    (``PERF.md`` section 6, PR 33).  No pick is dropped."""
    held = sizes.experts_held
    w1, w3, w2 = kernels                       # (held, d, f) x 2, (held, f, d)
    here = selected[..., None] == sizes.first_expert + jnp.arange(held)
    if valid is not None:
        here = here & valid[:, None, None]
    gate = jnp.where(here, weights[..., None], 0.0).sum(1)    # (N, held)
    h = (jax.nn.silu(jnp.einsum("nd,edf->nef", m, w1))
         * jnp.einsum("nd,edf->nef", m, w3))
    h = (h * gate[..., None]).astype(m.dtype)
    return jnp.einsum("nef,efd->nd", h, w2), here.sum((0, 1))


class SparseExperts(nn.Module):
    sizes: Sizes

    @nn.compact
    def __call__(self, m, valid=None):
        z = self.sizes
        lead, d = m.shape[:-1], m.shape[-1]
        flat = m.reshape(-1, d)
        with jax.named_scope("net.moe.route"):
            router = Kernel((d, z.experts), name="router")()
            selected, weights = route(flat, router, z)
        with jax.named_scope("net.moe.experts"):
            kernels = Experts(z.experts_held, z.expert_width,
                              name="experts")(d)
            y, counts = held_experts(
                flat, selected, weights, kernels, z,
                None if valid is None else valid.reshape(-1))
        with jax.named_scope("net.moe.shared"):
            y = y + SwiGLU(z.expert_width * z.shared_experts,
                           name="shared")(flat)
        return y.reshape(lead + (d,)), counts


class Layer(nn.Module):
    sizes: Sizes
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, h, cache=None, pos=None, valid=None):
        z = self.sizes
        a = RMSNorm(z.eps, name="pre_attn_norm")(h)
        o, cache = Attention(z, self.kind, name="attn")(a, cache, pos)
        h = h + RMSNorm(z.eps, name="post_attn_norm")(o)
        m = RMSNorm(z.eps, name="pre_mlp_norm")(h)
        if self.dense:
            with jax.named_scope("net.mlp"):
                y = SwiGLU(z.dense_width, name="mlp")(m)
            counts = jnp.zeros((0,), jnp.int32)
        else:
            y, counts = SparseExperts(z, name="moe")(m, valid)
        h = h + RMSNorm(z.eps, name="post_mlp_norm")(y)
        return h, cache, counts


class SequencePolicyNet(nn.Module):
    sizes: Sizes

    @property
    def sequence_length(self):
        return self.sizes.sequence_length

    def init_hidden(self, batch_shape=()):
        """The actor's key-value cache, empty: the position to write
        next and every layer's keys and values."""
        z = self.sizes
        shape = tuple(batch_shape) + (
            len(z.layer_types), z.sequence_length, z.kv_heads, z.head_dim)
        return {"pos": jnp.zeros(tuple(batch_shape), jnp.int32),
                "k": jnp.zeros(shape, jnp.float32),
                "v": jnp.zeros(shape, jnp.float32)}

    @nn.compact
    def __call__(self, tokens, hidden=None):
        z = self.sizes
        whole = hidden is None
        table = self.param(
            "embedding", nn.initializers.normal(1.0 / math.sqrt(z.hidden)),
            (z.vocab, z.hidden))
        # a window's positions past its episode's end come as -1
        # (``ops.losses.forward_prediction``): they read token 0 and
        # take no expert
        valid = (tokens >= 0) if whole else None
        # mup_enabled: the embedding is scaled by sqrt(hidden_size)
        h = table[jnp.maximum(tokens, 0)] * jnp.asarray(
            math.sqrt(z.hidden), table.dtype)
        layer = nn.remat(
            Layer, policy=jax.checkpoint_policies.save_only_these_names(
                KEPT)) if whole else Layer
        pos = None if whole else hidden["pos"]
        keys, values, counts = [], [], []
        for i, kind in enumerate(z.layer_types):
            cache = None if whole else (hidden["k"][:, i], hidden["v"][:, i])
            h, cache, c = layer(z, kind, i < z.dense_layers,
                                name=f"layer_{i}")(h, cache, pos, valid)
            counts.append(c)
            if not whole:
                keys.append(cache[0])
                values.append(cache[1])
        with jax.named_scope("net.head"):
            feats = RMSNorm(z.eps, name="final_norm")(h)
            kernel = Kernel((z.hidden, z.vocab), name="head")()
            value = jnp.tanh(jnp.dot(
                feats, Kernel((z.hidden, 1), name="value_head")()
            ).astype(jnp.float32))
            if whole:
                policy = FactoredPolicy(feats, kernel)
            else:
                policy = jnp.dot(feats, kernel).astype(jnp.float32)
        out = {"policy": policy, "value": value}
        if whole:
            # positions routed to each held expert, by expert layer
            out["expert_load"] = jnp.stack(
                [c for c in counts if c.shape[0]])
            out["expert_picks"] = (
                valid.sum() * z.experts_per_token).astype(jnp.float32)
        else:
            out["hidden"] = {
                "pos": pos + 1,
                "k": jnp.stack(keys, 1).astype(hidden["k"].dtype),
                "v": jnp.stack(values, 1).astype(hidden["v"].dtype)}
        return out


def sequence_net(preset):
    """The module at a named size (``PRESETS``)."""
    if preset not in PRESETS:
        raise ValueError(
            f"no sequence-net preset {preset!r}; there are "
            f"{sorted(PRESETS)}")
    return SequencePolicyNet(PRESETS[preset])
