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

A preset may declare a second form of the same decoder, and each part
of it is chosen at trace time from what its ``Sizes`` say, never from
its name: layers of ``LATENT`` attention (``LatentAttention``: a
latent rank, a rotary part and a value width that are not nought), no
norm after a branch, no embedding scale, kernels PUBLISHED for pairs
rotated interleaved (the parameters keep that convention; their
columns are put half-split where they are read, ``half_split``),
and a next-next-token module after the trunk (``NextNext``), whose
second prediction a position comes back factored under ``mtp`` from
the whole-window pass and takes a cross-entropy term beside the RL
loss (``ops.losses.nextn_term``).  Those equations follow the
DeepSeek-V3 family (JoyAI-LLM-Flash's ``config.json``; ASSUMED items in
``benchmarks/configs/joyai_flash_ep16.yaml``, the plain reference
``benchmarks/reference/joyai_net.py``).

A third form, chosen the same way: layers of ``LINEAR`` attention, a
gated delta-rule recurrence along the window (``DeltaMixer``: a matrix
state a head, short causal convolutions, a gated norm), beside full
attention layers in one net; every layer dense; branches normed coming
out only; the attention's q/k norm over the whole projection and no
output gate; and each mixer holding the chip's share of the heads
(``heads_held``), as an expert layer holds its share of the experts:
what the absent heads would add to a branch is left out, and no code
stands in for the other chip or its all-reduce.  Those equations follow
Gated DeltaNet (arXiv:2412.06464) in the Olmo family's block
(Olmo-Hybrid-7B's ``config.json``; ASSUMED items in
``benchmarks/configs/olmo_hybrid_tp2.yaml``, the plain reference
``benchmarks/reference/olmo_hybrid_net.py``).

The expert layer is TOLD which experts it holds (``first_expert``,
``experts_held``): it routes over all ``experts``, normalises the
weights over every selected expert, held or not, and computes its own
experts' part of the result for the positions routed to them -- the
chip's share under expert parallelism, on one chip without the
exchange.  No token is dropped and no capacity exists: every pick that
falls on a held expert is computed (``held_experts``: over the picks
alone where the step is lowered for a TPU, by every held expert over
every position elsewhere); only a window's padding past its episode's
end (token -1) takes no expert.

Two call shapes, one set of parameters:

  * ``module(tokens (B, T), None)`` -- the learner's pass over whole
    windows.  Attention skips what causality and the window hide: on a
    TPU, at lane-wide shapes, as ONE fused kernel a layer whose scores
    never leave the chip's fast memory (``fused_attention``), each of
    its operands going from its projection to the kernel in ONE pass
    in the compute dtype, norm and rotation inside it (``_turn_pass``);
    everywhere else in query blocks of plain XLA (``turned``,
    ``blocked_attention``: the statement the kernels are held to).  Layers are rematerialised (all
    but the kernel's output and the widest products with weight
    matrices, ``KEPT_NAMES``); the policy comes back FACTORED
    (``ops.losses.FactoredPolicy``: trunk features and the head's
    kernel), so that the ``(B * T, vocab)`` logits never exist whole.
  * ``module(token (N,), hidden)`` -- the actor's one-token step through
    a cache carried as the seat's ``hidden`` (``init_hidden``: every
    key-value head's keys and values, or a latent layer's latent and
    rotated key, or a delta layer's state and last convolution inputs,
    each kind of layer its own entries): dense logits for that position,
    the cache advanced by one.  The next-next-token module is not run:
    an actor drafts nothing.

``sequence_length`` (the cache's positions, the longest episode) is how
the module declares itself a sequence net: ``TPUModel.is_sequence``.
"""

import math
from collections import Counter
from functools import lru_cache, partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.losses import FactoredPolicy, rows_on

SLIDING, FULL = "sliding_attention", "full_attention"
LATENT = "latent_attention"
LINEAR = "linear_attention"


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
    # what a latent-attention net declares beside (``head_dim`` is then
    # the part of a query-key head that takes no rotation)
    latent_q: int = 0              # rank of the queries' latent
    latent_kv: int = 0             # rank of the keys' and values' latent
    rope_dim: int = 0              # a head's rotary part; ONE key for all
    value_dim: int = 0             # a value head's width
    rope_interleave: bool = False  # rotate pairs (2i, 2i+1), not (i, i+D/2)
    post_norms: bool = True        # an RMSNorm after each branch too
    embed_scale: bool = True       # h = E[tokens] * sqrt(hidden)
    nextn_modules: int = 0         # next-next-token modules (0 or 1)
    # what a net with ``LINEAR`` layers declares beside
    delta_key_dim: int = 0         # a delta head's key (and query) width
    delta_value_dim: int = 0       # its value width
    conv_taps: int = 0             # taps of the short causal convolutions
    delta_chunk: int = 64          # positions a chunk of the recurrence
    # the chip's share of every mixer's heads (0: all of them): the
    # weights ARE the share's
    heads_held: int = 0
    pre_norms: bool = True         # an RMSNorm before each branch
    attention_gate: bool = True    # o = o * sigmoid(a Wg)
    qk_norm_whole: bool = False    # q/k normed over the projection, not a head


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
    # JoyAI-LLM-Flash (jdopensource, joyai_llm_flash, 48B-A2.7B) at
    # published widths: one chip's share of sixteen -- experts 0-15 of
    # 256, rows 0-16,159 of the vocabulary, the leading dense layer, four
    # expert layers and the next-next-token module
    "joyai_flash_ep16": Sizes(
        vocab=16160, hidden=2048, layer_types=(LATENT,) * 5,
        dense_layers=1, heads=32, kv_heads=32, head_dim=128,
        dense_width=7168, expert_width=768, experts=256,
        experts_held=16, first_expert=0, experts_per_token=8,
        shared_experts=1, route_scale=2.5, window=0,
        sequence_length=8192, rope_theta=32e6, eps=1e-6,
        latent_q=1536, latent_kv=512, rope_dim=64, value_dim=128,
        rope_interleave=True, post_norms=False, embed_scale=False,
        nextn_modules=1),
    # Olmo-Hybrid-7B (allenai, olmo_hybrid) at published widths: one
    # chip's share of two -- heads 0-14 of every mixer's 30, rows
    # 0-12,543 of the vocabulary, one period of three delta layers and
    # one full attention, every MLP whole
    "olmo_hybrid_tp2": Sizes(
        vocab=12544, hidden=3840, layer_types=(LINEAR,) * 3 + (FULL,),
        dense_layers=4, heads=30, kv_heads=30, head_dim=128,
        dense_width=11008, expert_width=0, experts=0, experts_held=0,
        first_expert=0, experts_per_token=0, shared_experts=0,
        route_scale=0.0, window=0, sequence_length=4096, eps=1e-6,
        embed_scale=False, delta_key_dim=96, delta_value_dim=192,
        conv_taps=4, heads_held=15, pre_norms=False, attention_gate=False,
        qk_norm_whole=True),
    # the same modules at test size (tier-1, CPU)
    "tiny_hybrid": Sizes(
        vocab=64, hidden=64, layer_types=(LINEAR, FULL, LINEAR),
        dense_layers=3, heads=4, kv_heads=4, head_dim=16, dense_width=128,
        expert_width=0, experts=0, experts_held=0, first_expert=0,
        experts_per_token=0, shared_experts=0, route_scale=0.0, window=0,
        sequence_length=32, eps=1e-6, attention_block=16,
        embed_scale=False, delta_key_dim=8, delta_value_dim=16,
        conv_taps=4, delta_chunk=8, heads_held=2, pre_norms=False,
        attention_gate=False, qk_norm_whole=True),
    "tiny_latent": Sizes(
        vocab=64, hidden=64, layer_types=(LATENT,) * 3, dense_layers=1,
        heads=4, kv_heads=4, head_dim=16, dense_width=128,
        expert_width=32, experts=8, experts_held=2, first_expert=0,
        experts_per_token=2, shared_experts=1, route_scale=2.5, window=0,
        sequence_length=32, rope_theta=32e6, eps=1e-6,
        attention_block=16, latent_q=48, latent_kv=32, rope_dim=8,
        value_dim=16, rope_interleave=True, post_norms=False,
        embed_scale=False, nextn_modules=1),
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


def _project(x, features, name, dtype=None):
    """``x W`` by the kernel ``name``, in ``x``'s dtype or the wider
    ``dtype`` the sums are handed out in."""
    return jnp.dot(x, Kernel((x.shape[-1], features), name=name)(),
                   preferred_element_type=dtype)


def _turns(positions, theta, rope):
    """Cosine and sine ``(..., rope / 2)`` of each pair's angle at
    ``positions (...)``: pair ``i`` of ``rope`` rotary numbers turns by
    ``position * theta^(-2i/rope)``, in float32."""
    freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = positions[..., None].astype(jnp.float32) * freq
    return jnp.cos(angle), jnp.sin(angle)


def rotate(x, positions, theta, rope=None):
    """Rotary positions on the last ``rope`` numbers (all, unless given)
    of each head of ``x (..., T, H, D)``, ``positions (..., T)``, in the
    half-split convention: pair ``i`` is ``(i, i + rope/2)`` of them
    and turns by ``position * theta^(-2i/rope)``; the numbers before
    them stay as they are.  Float32 inside, ``x``'s dtype out.  This is
    the ONE convention the module rotates in: a net whose published
    kernels pair ``(2i, 2i + 1)`` has their columns put in this order
    first (``half_split``).  It is the plain statement (a CPU, the
    actors' one-token step); over a whole window on a TPU the same
    rotation rides the operand's one pass to the attention kernel
    (``_turn_pass``), since XLA makes five passes of this one."""
    d = x.shape[-1]
    rope = d if rope is None else rope
    plain, half = d - rope, rope // 2
    cos, sin = (table[..., None, :]
                for table in _turns(positions, theta, rope))
    x32 = x.astype(jnp.float32)
    first, second = x32[..., plain:plain + half], x32[..., plain + half:]
    turned = [first * cos - second * sin, second * cos + first * sin]
    return jnp.concatenate(
        ([x32[..., :plain]] if plain else []) + turned, -1).astype(x.dtype)


def half_split(kernel, heads, rope):
    """``kernel (d, heads * (plain + rope))`` with the last ``rope``
    columns of each head, PUBLISHED as pairs ``(2i, 2i + 1)``, put in
    the half-split order (every pair's first number, then every pair's
    second).  A score is a sum over a query's and a key's rotary
    numbers: the same order on both leaves every score as it was.
    Stated as a product with a matrix of noughts and ones, which moves
    numbers and rounds none (read on the chip, a latent layer forward
    and backward: 36.3 ms; as a gather of columns 36.8; as strided
    slices and a concatenation 36.8, and the compiler then allocated
    every expert layer's buffers at once, 3.5 GB more)."""
    w = kernel.reshape(kernel.shape[0], heads, -1)
    width = w.shape[-1]
    plain = width - rope
    order = np.r_[:plain, plain:width:2, plain + 1:width:2]
    pick = np.zeros((width, width), np.float32)
    pick[order, np.arange(width)] = 1
    return jnp.einsum(
        "dhc,ce->dhe", w, jnp.asarray(pick, kernel.dtype),
        precision=lax.Precision.HIGHEST).reshape(kernel.shape)


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
L2_EPS = 1e-6                   # under a delta head's l2 norm of q and k
# what a rematerialised layer keeps of its fused attention going
# forward: the kernel's output and one log-sum-exp a query (68 MB a
# layer at the published widths), so that the backward pass does not
# run the forward kernel again
KEPT = "fused_attention_out"
# the rule: a rematerialised layer keeps the results of its widest
# products with weight matrices (a SwiGLU's three; a delta mixer's q, k,
# v and gate projections and ``Wo``'s result, each in the compute dtype
# as the product hands it out) and its attention kernel's output;
# elementwise work, recurrences and every float32 pass over the window
# are made again.  A name that nothing coming back reads costs nothing:
# the backward pass's partial evaluation drops it
KEPT_NAMES = (KEPT, "mlp_gate", "mlp_up", "mlp_out",
              "delta_q", "delta_k", "delta_v", "delta_gate", "delta_out")
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
    ``D``-wide query-key heads (``block``: the module's constant unless
    given), or None where the positions are not whole blocks of whole
    lanes or a head is not whole half-lanes, one lane at least (a
    latent head's 192 goes in as it is: zero-padded to 256 the kernels
    took 26.8 ms a layer on the chip where they take 26.0)."""
    block = block or min(FUSED_BLOCK, T)
    compute = min(FUSED_COMPUTE, block)
    if (T % block or block % compute or compute % LANES
            or D < LANES or D % (LANES // 2)):
        return None
    return block, compute


class Turn(NamedTuple):
    """What an operand of attention takes between its projection and the
    attention itself: an RMSNorm over each head where a scale is handed
    beside it (``eps`` its epsilon), then the rotation of each head's
    last ``rope`` numbers (``rotate``; 0: none)."""
    rope: int = 0
    theta: float = 0.0
    eps: float = 0.0


def turned(x, turn, gain=None):
    """``turn`` of ``x (B, T, ..., D)`` in plain XLA, every position at
    its own place in the window: the statement the kernel pass
    (``_turn_pass``) is held to."""
    if gain is not None:
        x = rms_norm(x, gain, turn.eps)
    if turn.rope:
        B, T = x.shape[:2]
        x = rotate(x.reshape(B, T, -1, x.shape[-1]), jnp.arange(T)[None],
                   turn.theta, turn.rope).reshape(x.shape)
    return x


def _partner(x, half):
    """Each rotary number's partner, for a tile of 128 lanes whose first
    ``2 * half`` are a head's two halves: lane ``i`` takes lane ``i +
    half`` in the first half, ``i - half`` in the second."""
    back = pltpu.roll(x, half, x.ndim - 1)
    if 2 * half == LANES:
        return back
    lane = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(
        lane < half, pltpu.roll(x, LANES - half, x.ndim - 1), back)


def _by_columns(half, first, plain, rotary):
    """``rotary()`` in the grid's column blocks from ``first`` on (those
    that hold rotary numbers), ``plain()`` in those before."""
    if not half:
        plain()
    elif not first:
        rotary()
    else:
        column = pl.program_id(2)
        pl.when(column < first)(plain)
        pl.when(column >= first)(rotary)


def _turn_forward_body(half, first, eps, x_ref, cos_ref, sin_ref, *refs):
    """One ``(rows, 128)`` tile of one head: the norm over the head (a
    head of 128, where ``eps`` is given), then ``x * cos + partner *
    sin`` against tables that carry the scale and the rotation's signs,
    in float32 in fast memory; read once, written once."""
    out_ref = refs[-1]
    x = x_ref[...].astype(jnp.float32)
    if eps is not None:
        x = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * refs[0][...]

    def store(y):
        out_ref[...] = y.astype(out_ref.dtype)

    _by_columns(
        half, first, lambda: store(x * cos_ref[...]),
        lambda: store(x * cos_ref[...] + _partner(x, half) * sin_ref[...]))


def _turn_backward_body(half, first, eps, ct_ref, cos_ref, sin_ref, *refs):
    """The forward body's transpose over the same tile: the partner of a
    partner is the number itself, so the rotation comes back as ``ct *
    cos + partner(ct * sin)``; then the norm's own transpose from the
    head as it went in, and this tile's part of the scale's gradient."""
    ct = ct_ref[...].astype(jnp.float32)

    def store(d):
        if eps is None:
            refs[0][...] = d.astype(refs[0].dtype)
            return
        x_ref, gain_ref, dx_ref, dgain_ref = refs
        x = x_ref[...].astype(jnp.float32)
        r = lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        normed, scaled = x * r, d * gain_ref[...]
        dx_ref[...] = (r * (scaled - normed * jnp.mean(
            scaled * normed, -1, keepdims=True))).astype(dx_ref.dtype)
        dgain_ref[...] = jnp.sum(d * normed, 0, keepdims=True)

    _by_columns(
        half, first, lambda: store(ct * cos_ref[...]),
        lambda: store(ct * cos_ref[...]
                      + _partner(ct * sin_ref[...], half)))


def _turn_call(body, shape, dtype, rows, operands, flat, parts, interpret):
    """``body`` over every ``(rows, 128)`` tile of every head of ``(B, H,
    T, D)``.  ``operands`` are pairs ``(kind, array)``: ``"tile"`` an
    array of that shape, the attention kernels' layout; ``"flat"`` the
    same numbers as a projection writes them, ``(B, T, H * D)`` (heads
    of whole tiles); ``"table"`` ``(T, whole tiles)``; ``"gain"`` ``(1,
    128)``.  The result is one more array, ``flat`` or not, and, with
    ``parts``, each tile's own ``(1, 128)`` sums."""
    B, H, T, D = shape
    columns = pl.cdiv(D, LANES)
    # heads innermost: a table's tile is fetched once for all of them
    specs = {
        "tile": pl.BlockSpec(
            (None, None, rows, LANES), lambda b, t, c, h: (b, h, t, c)),
        "flat": pl.BlockSpec(
            (None, rows, LANES), lambda b, t, c, h: (b, t, h * columns + c)),
        "table": pl.BlockSpec((rows, LANES), lambda b, t, c, h: (t, c)),
        "gain": pl.BlockSpec((1, LANES), lambda b, t, c, h: (0, 0))}
    out_specs = specs["flat" if flat else "tile"]
    out_shape = jax.ShapeDtypeStruct(
        (B, T, H * D) if flat else shape, dtype)
    if parts:
        out_specs = (out_specs, pl.BlockSpec(
            (None, None, None, 1, LANES), lambda b, t, c, h: (b, h, t, 0, 0)))
        out_shape = (out_shape, jax.ShapeDtypeStruct(
            (B, H, T // rows, 1, LANES), jnp.float32))
    return pl.pallas_call(
        body, grid=(B, T // rows, columns, H),
        in_specs=[specs[kind] for kind, _ in operands], out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, name="turn_pass",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4),
    )(*(array for _, array in operands))


class _Tiles(NamedTuple):
    """One pass's geometry: the heads, the lanes of a rotary half (0:
    no rotation) in the tiles from ``first`` on, the norm's epsilon
    (None: no norm), the rows a tile, and whether the projection's side
    is ``flat``."""
    heads: int
    half: int
    first: int
    eps: Optional[float]
    rows: int
    flat: bool


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _turn_tiles(x, cos, sin, gain, z, interpret):
    B, T = x.shape[0], x.shape[1 if z.flat else 2]
    shape = (B, z.heads, T, x.shape[-1] // (z.heads if z.flat else 1))
    operands = [("flat" if z.flat else "tile", x), ("table", cos),
                ("table", sin)] + ([] if z.eps is None else [("gain", gain)])
    return _turn_call(
        partial(_turn_forward_body, z.half, z.first, z.eps), shape, x.dtype,
        z.rows, operands, False, False, interpret)


def _turn_tiles_forward(x, cos, sin, gain, z, interpret):
    # the head as it went in is kept for the norm's transpose alone
    return (_turn_tiles(x, cos, sin, gain, z, interpret),
            (cos, sin, gain, None if z.eps is None else x))


def _turn_tiles_backward(z, interpret, kept, ct):
    cos, sin, gain, x = kept
    body = partial(_turn_backward_body, z.half, z.first, z.eps)
    operands = [("tile", ct), ("table", cos), ("table", sin)]
    tables = (jnp.zeros_like(cos), jnp.zeros_like(sin))
    if z.eps is None:
        return (_turn_call(body, ct.shape, ct.dtype, z.rows, operands,
                           z.flat, False, interpret),) + tables + (None,)
    operands += [("flat" if z.flat else "tile", x), ("gain", gain)]
    dx, parts = _turn_call(
        body, ct.shape, ct.dtype, z.rows, operands, z.flat, True, interpret)
    return (dx,) + tables + (parts.sum((0, 1, 2)).astype(gain.dtype),)


_turn_tiles.defvjp(_turn_tiles_forward, _turn_tiles_backward)


def _turn_pass(x, turn, gain, scale, rows, interpret):
    """``turned`` times ``scale`` of ``x (B, T, H, D)``, a projection's
    result, as ``(B, H, T, D)``, the attention kernels' layout, in ONE
    pass of a kernel of this module: each ``(rows, 128)`` tile of a
    head is read once in ``x``'s dtype, normed, rotated and scaled in
    float32 in fast memory, and written once; coming back the same
    pass transposed (``custom_vjp``).  Heads of whole tiles are read
    where the projection wrote them (and their cotangents written
    there); a head of 192 goes through XLA's transpose, which costs
    nothing where the compiler lays the projection's result out
    head-major (it does at a batch of one).  The kernel takes rotary
    numbers that are a head's last, within one tile and behind whole
    tiles, and a normed head of one tile; any other shape, and an
    operand with nothing to do but a scale, is left to XLA
    (``turned``)."""
    B, T, H, D = x.shape
    plain, half = D - turn.rope, turn.rope // 2
    if not ((turn.rope or gain is not None) and plain % LANES == 0
            and turn.rope <= LANES and (gain is None or D == LANES)):
        x = turned(x, turn, gain)
        x = x if scale == 1.0 else (x * scale).astype(x.dtype)
        return x.transpose(0, 2, 1, 3)
    wide = -(-D // LANES) * LANES
    cos = jnp.full((T, wide), scale, jnp.float32)
    sin = jnp.zeros((T, wide), jnp.float32)
    if half:
        c, s = (scale * table
                for table in _turns(jnp.arange(T), turn.theta, turn.rope))
        cos = cos.at[:, plain:D].set(jnp.concatenate([c, c], -1))
        sin = sin.at[:, plain:D].set(jnp.concatenate([-s, s], -1))
    if gain is not None:
        gain = gain.astype(jnp.float32)[None]
    flat = D % LANES == 0
    x = x.reshape(B, T, H * D) if flat else x.transpose(0, 2, 1, 3)
    return _turn_tiles(x, cos, sin, gain, _Tiles(
        H, half, plain // LANES, None if gain is None else turn.eps, rows,
        flat), interpret)


def fused_attention(q, k, v, window, block=None, interpret=False,
                    turns=(Turn(), Turn()), gains=(None, None)):
    """The same attention as ``blocked_attention`` over the same
    ``q (B, T, KV, G, D)``, ``k (B, T, KV, D)`` and ``v (B, T, KV, Dv)``,
    as one kernel a layer: online softmax in float32 in the chip's fast
    memory, blocks of ``block`` x ``block`` that causality or the window
    hide whole skipped, the probabilities meeting ``v`` in ``v``'s
    dtype, and a backward kernel of its own that makes the scores again
    from q, k and one log-sum-exp a query.  No array of score size is
    written in either direction.  ``q`` and ``k`` come as their
    projections wrote them (a latent net's with their rotary columns
    already half-split) and take their ``turns`` (and ``gains``) on the
    way to the kernel's ``(heads, T, D)``, each in one pass
    (``_turn_pass``; q's carries the scale, which the kernel does not
    apply).  ``T`` is a multiple of
    ``block`` (the module's constant unless given), ``block`` of 128,
    ``D`` and ``Dv`` of 64 and 128 at least; ``interpret`` runs the
    kernels' bodies as plain JAX (tier-1, on the CPU)."""
    B, T, KV, G, D = q.shape
    block, compute = _fused_blocks(T, D, block)
    attend = _fused_kernel(T, window, G, block, compute, interpret)
    q = _turn_pass(q.reshape(B, T, KV * G, D), turns[0], gains[0],
                   1.0 / math.sqrt(D), block, interpret)
    k = _turn_pass(k, turns[1], gains[1], 1.0, block, interpret)
    o = jax.vmap(jax.vmap(attend))(          # over batch and kv head
        q.reshape(B, KV, G, T, D), k, v.transpose(0, 2, 1, 3))
    return o.transpose(0, 3, 1, 2, 4)        # (B, T, KV, G, Dv)


def window_attention(q, k, v, window, block, turns=(Turn(), Turn()),
                     gains=(None, None)):
    """Attention over a whole window, ``q`` and ``k`` taking their
    ``turns`` first, by the path the program can take where it is
    lowered: kernels on a TPU when the shapes are whole lanes (one pass
    an operand, then the fused attention), plain XLA everywhere else (a
    CPU, a head narrower than a lane: ``turned``, then query blocks of
    ``block``).  Chosen per lowering platform, so a program compiled
    for a described chip from a CPU process takes the chip's path."""
    def plain(q, k, v, gains):
        return blocked_attention(
            turned(q, turns[0], gains[0]), turned(k, turns[1], gains[1]), v,
            window, block)

    def fused(q, k, v, gains, interpret=False):
        return fused_attention(q, k, v, window, None, interpret, turns, gains)

    if _fused_blocks(q.shape[1], q.shape[-1]) is None:
        return plain(q, k, v, gains)
    return lax.platform_dependent(q, k, v, gains, default=plain, tpu=fused)


class Scale(nn.Module):
    """An RMSNorm's scale, handed out as it is (the norm itself runs
    where its operand is turned)."""
    width: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.width,))


def held_heads(sizes):
    """``(query heads, key-value heads)`` a mixer holds here: all of
    them, or the chip's share where the net declares one."""
    heads = sizes.heads_held or sizes.heads
    return heads, sizes.kv_heads * heads // sizes.heads


class Attention(nn.Module):
    """Grouped-query attention over the heads held here.  q and k are
    normed a head at a time (one scale of ``head_dim``), or, where the
    net declares ``qk_norm_whole``, over the whole projection before
    the split into heads: the one quantity of this layer that is not a
    head's own, so a share takes its mean square over the columns it
    holds (the deployment's all-reduce of one scalar a position is left
    out with every exchange)."""
    sizes: Sizes
    kind: str

    @nn.compact
    def __call__(self, a, cache=None, pos=None):
        z = self.sizes
        heads, kv_heads = held_heads(z)
        groups = heads // kv_heads
        window = z.window if self.kind == SLIDING else 0
        scope = "net.attention.window" if window else "net.attention.full"
        with jax.named_scope(scope):
            lead = a.shape[:-1]
            q = _project(a, heads * z.head_dim, "q")
            k = _project(a, kv_heads * z.head_dim, "k")
            v = _project(a, kv_heads * z.head_dim, "v").reshape(
                lead + (kv_heads, z.head_dim))
            if z.qk_norm_whole:
                q = rms_norm(q, Scale(q.shape[-1], name="q_norm")(), z.eps)
                k = rms_norm(k, Scale(k.shape[-1], name="k_norm")(), z.eps)
                gains = (None, None)
            else:
                gains = (Scale(z.head_dim, name="q_norm")(),
                         Scale(z.head_dim, name="k_norm")())
            q = q.reshape(lead + (kv_heads, groups, z.head_dim))
            k = k.reshape(lead + (kv_heads, z.head_dim))
            gate = jax.nn.sigmoid(_project(
                a, heads * z.head_dim, "gate")) if z.attention_gate else None
            if cache is None:
                # a whole window: (B, T, ...); norm and rotation ride
                # each operand's one pass to the attention
                B, T = lead
                turn = Turn(z.head_dim if window else 0, z.rope_theta, z.eps)
                o = window_attention(q, k, v, window, z.attention_block,
                                     (turn, turn), gains)
                o = o.reshape(B, T, heads * z.head_dim)
            else:
                # one token a row, through the cache: (N, ...)
                if not z.qk_norm_whole:
                    q = rms_norm(q, gains[0], z.eps)
                    k = rms_norm(k, gains[1], z.eps)
                if window:
                    q = rotate(q.reshape(-1, 1, heads, z.head_dim),
                               pos[:, None], z.rope_theta).reshape(q.shape)
                    k = rotate(k[:, None], pos[:, None], z.rope_theta)[:, 0]
                keys, values = cache                 # (N, S, KV, D)
                s = jnp.arange(keys.shape[1])
                here = (s[None] == pos[:, None])[..., None, None]
                keys = jnp.where(here, k[:, None].astype(keys.dtype), keys)
                values = jnp.where(
                    here, v[:, None].astype(values.dtype), values)
                cache = (keys, values)
                scores = jnp.einsum(
                    "nkgd,nskd->nkgs", q.astype(jnp.float32),
                    keys.astype(jnp.float32)) / math.sqrt(z.head_dim)
                seen = _visible(pos[:, None], s[None], window)
                scores = jnp.where(seen[:, None, None], scores, -1e30)
                p = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum("nkgs,nskd->nkgd", p,
                               values.astype(jnp.float32))
                o = o.reshape(-1, heads * z.head_dim).astype(a.dtype)
            o = _project(o if gate is None else o * gate, z.hidden, "o")
        return o, cache


class LatentAttention(nn.Module):
    """Attention through two low-rank latents: queries from a normed
    latent of ``latent_q``, keys and values from ONE normed latent of
    ``latent_kv`` a position, and a rotary key of ``rope_dim`` that all
    heads share.  A head's query and key are ``head_dim`` numbers that
    take no rotation beside ``rope_dim`` that do; its value is
    ``value_dim`` wide.  Full causal attention, no gate.

    The PARAMETERS keep the published convention, names and shapes (a
    checkpoint and the plain reference fit): ``q_b``'s and ``kv_a``'s
    rotary columns are pairs ``(2i, 2i + 1)``.  ``__call__`` puts those
    columns half-split as it reads the two kernels (``half_split``: the
    same order for queries and the key, so no score moves), and both
    call shapes then rotate half-split (``rotate``); the actor's cache
    holds the rotated key in that order.

    The whole-window pass makes every head's keys and values from the
    latent by TWO products of ``kv_b``'s two column ranges (each writes
    what the attention reads; nothing is written to be cut apart) and
    attends as any layer does (``window_attention``: each head its own
    key-value head; q's rotation rides its pass to the kernel).  The one-token step caches what a
    position IS -- its normed latent and its rotated key, ``latent_kv +
    rope_dim`` numbers -- and attends in the latent: the query is taken
    through the keys' half of ``kv_b`` first and the result through the
    values' half after, the same sums in another order."""
    sizes: Sizes

    @nn.compact
    def __call__(self, a, cache=None, pos=None):
        z = self.sizes
        nope, rope, wide = z.head_dim, z.rope_dim, z.value_dim
        with jax.named_scope("net.attention.latent"):
            lead = a.shape[:-1]
            cq = RMSNorm(z.eps, name="q_norm")(
                _project(a, z.latent_q, "q_a"))
            q_b = Kernel((z.latent_q, z.heads * (nope + rope)), name="q_b")()
            kv_a = Kernel((a.shape[-1], z.latent_kv + rope), name="kv_a")()
            if z.rope_interleave:
                q_b = half_split(q_b, z.heads, rope)
                kv_a = half_split(kv_a, 1, rope)
            q = jnp.dot(cq, q_b).reshape(lead + (z.heads, nope + rope))
            kv_a = jnp.dot(a, kv_a)
            latent = RMSNorm(z.eps, name="kv_norm")(kv_a[..., :z.latent_kv])
            key = kv_a[..., None, z.latent_kv:]        # (..., 1, rope)
            kv_b = Kernel((z.latent_kv, z.heads * (nope + wide)),
                          name="kv_b")().reshape(
                              z.latent_kv, z.heads, nope + wide)
            if cache is None:
                # a whole window: (B, T, ...)
                B, T = lead
                k = jnp.concatenate([
                    jnp.einsum("btc,chd->bthd", latent, kv_b[..., :nope]),
                    jnp.broadcast_to(
                        rotate(key, jnp.arange(T)[None], z.rope_theta),
                        (B, T, z.heads, rope))], -1)
                v = jnp.einsum("btc,chd->bthd", latent, kv_b[..., nope:])
                o = window_attention(
                    q[:, :, :, None], k, v, 0, z.attention_block,
                    (Turn(rope, z.rope_theta), Turn()))
                o = o.reshape(B, T, z.heads * wide)
            else:
                # one token a row, through the cache: (N, ...)
                q_rope = rotate(
                    q[:, None, :, nope:], pos[:, None], z.rope_theta)[:, 0]
                key = rotate(key[:, None], pos[:, None], z.rope_theta)[:, 0, 0]
                latents, keys = cache        # (N, S, latent_kv), (N, S, rope)
                s = jnp.arange(latents.shape[1])
                here = (s[None] == pos[:, None])[..., None]
                latents = jnp.where(
                    here, latent[:, None].astype(latents.dtype), latents)
                keys = jnp.where(
                    here, key[:, None].astype(keys.dtype), keys)
                cache = (latents, keys)
                kv_b = kv_b.astype(jnp.float32)
                q_latent = jnp.einsum(
                    "nhd,chd->nhc", q[..., :nope].astype(jnp.float32),
                    kv_b[..., :nope])
                scores = (
                    jnp.einsum("nhc,nsc->nhs", q_latent,
                               latents.astype(jnp.float32))
                    + jnp.einsum("nhr,nsr->nhs", q_rope.astype(jnp.float32),
                                 keys.astype(jnp.float32))
                ) / math.sqrt(nope + rope)
                seen = _visible(pos[:, None], s[None], 0)
                p = jax.nn.softmax(
                    jnp.where(seen[:, None], scores, -1e30), axis=-1)
                o = jnp.einsum("nhs,nsc->nhc", p,
                               latents.astype(jnp.float32))
                o = jnp.einsum("nhc,chd->nhd", o, kv_b[..., nope:])
                o = o.reshape(-1, z.heads * wide).astype(a.dtype)
            o = _project(o, z.hidden, "o")
        return o, cache


def short_conv(x, kernel, before=None):
    """The causal depthwise convolution over time of ``x (..., T, C)``
    with ``kernel (taps, C)``, one filter a channel: ``y_t = sum_j
    kernel[j] * x_{t - taps + 1 + j}``.  ``before (..., taps - 1, C)``
    is what came before the first position (an episode's start:
    zeros)."""
    taps, T = kernel.shape[0], x.shape[-2]
    if before is None:
        before = jnp.zeros(x.shape[:-2] + (taps - 1, x.shape[-1]), x.dtype)
    padded = jnp.concatenate([before.astype(x.dtype), x], -2)
    return sum(kernel[j] * padded[..., j:j + T, :] for j in range(taps))


def delta_step(state, q, k, v, g, beta):
    """One position of the gated delta rule, a head at a time:
    ``state (N, H, dv, dk)`` in float32, ``q, k (N, H, dk)``, ``v (N,
    H, dv)``, ``g, beta (N, H)``:

        S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
        o_t = S_t q_t

    -> ``(S_t, o_t (N, H, dv))``.  The statement the chunk-wise pass
    (``delta_scan``) is held to, and the actors' one-token step."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    alpha, beta = jnp.exp(g)[..., None, None], beta[..., None, None]
    seen = jnp.einsum("nhvk,nhk->nhv", state, k)
    state = alpha * (state - beta * seen[..., None] * k[..., None, :]) \
        + beta * v[..., None] * k[..., None, :]
    return state, jnp.einsum("nhvk,nhk->nhv", state, q)


@jax.custom_vjp
def unit_lower_inverse(lower):
    """``(I + L)^-1`` of ``lower (..., C, C)``, float32 and nought on
    and above the diagonal, by forward substitution a row at a time:
    row ``r`` of the inverse is ``e_r - L[r] X`` over the rows above it,
    which are final by then.  Multiplied and summed on the vector unit,
    so float32 to the bit on a chip whose matrix unit would round the
    operands, and stable where ``L`` is large (keys that repeat,
    written at ``beta`` near 2: a series in ``L``'s powers cancels to
    nothing there).  Coming back it is two products
    (``_inverse_backward``), not a loop transposed."""
    eye = jnp.eye(lower.shape[-1], dtype=lower.dtype)

    def row(r, inverse):
        weights = lax.dynamic_index_in_dim(lower, r, -2, keepdims=False)
        above = (weights[..., None] * inverse).sum(-2, keepdims=True)
        return lax.dynamic_update_slice_in_dim(
            inverse, lax.dynamic_slice_in_dim(eye, r, 1) - above, r, -2)

    return lax.fori_loop(1, lower.shape[-1], row,
                         jnp.broadcast_to(eye, lower.shape))


def _inverse_forward(lower):
    inverse = unit_lower_inverse(lower)
    return inverse, inverse


def _inverse_backward(inverse, cotangent):
    # d(A^-1) = -A^-1 dA A^-1; what falls on or above the diagonal
    # belongs to no entry of ``lower``
    turned = jnp.swapaxes(inverse, -1, -2)
    product = partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    return (-jnp.tril(product(product(turned, cotangent), turned), -1),)


unit_lower_inverse.defvjp(_inverse_forward, _inverse_backward)


def delta_scan(q, k, v, g, beta, chunk):
    """The gated delta rule (``delta_step``) over whole windows from a
    state of nought, ``q, k (B, T, H, dk)``, ``v (B, T, H, dv)``,
    ``g, beta (B, T, H)`` in float32 -> ``o (B, T, H, dv)``, in its
    chunk-wise parallel form (Gated DeltaNet, arXiv:2412.06464,
    section 3).  Within a chunk of ``chunk`` positions, with ``G_r``
    the running sum of ``g`` and ``S`` the state the chunk starts from,

        w_r = beta_r (v_r - exp(G_r) S k_r
                      - sum_{i<r} exp(G_r - G_i) (k_i . k_r) w_i)
        S_r = exp(G_r) S + sum_{i<=r} exp(G_r - G_i) w_i k_i^T
        o_r = exp(G_r) S q_r + sum_{i<=r} exp(G_r - G_i) (k_i . q_r) w_i

    so the ``w`` of a chunk solve ONE unit lower-triangular system,
    ``(I + L) W = diag(beta) (V - diag(exp G) K S^T)`` with ``L[r, i] =
    beta_r exp(G_r - G_i) (k_r . k_i)`` below the diagonal.  Its
    inverse (``unit_lower_inverse``), the products that do not see
    ``S`` and every decay are made for all chunks at once; a
    ``lax.scan`` over the chunks then carries ``S`` in float32 through
    three small products a chunk.  The triangular system, its
    inverse's two products and every decay are float32, and a decay is
    only ever ``exp(G_r - G_i)`` for ``i <= r``, never a reciprocal of
    a cumulative decay (a chunk's ``exp(-G)`` overflows where the
    decay is strong); the other products' operands are in ``q``'s dtype.
    The backward pass is this function's derivative.  A window that is
    no whole number of chunks is padded behind with positions that
    write nothing (``beta = 0``) and decay nothing (``g = 0``)."""
    B, T, H, _ = q.shape
    dtype, f32 = q.dtype, jnp.float32
    pad = -T % chunk
    n = (T + pad) // chunk

    def chunks(x):       # (B, T, H, ...) -> (n, B, H, chunk, ...)
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    G = jnp.cumsum(g.astype(f32), -1)                    # (n, B, H, C)
    beta = beta.astype(f32)
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None]
    upto = row >= col                                    # i <= r
    # exp(G_r - G_i) where i <= r, nought elsewhere; the difference is
    # masked BEFORE the exponential, whose cotangent would else be
    # nought times infinity where i > r
    decay = jnp.where(upto, jnp.exp(jnp.where(
        upto, G[..., :, None] - G[..., None, :], 0.0)), 0.0)
    kk = jnp.einsum("...rd,...id->...ri", k, k, preferred_element_type=f32)
    lower = jnp.where(row > col, beta[..., None] * decay * kk, 0.0)
    inverse = unit_lower_inverse(lower) * beta[..., None, :]
    since = jnp.exp(G)                # exp(G_r): decay since the chunk's start
    solved = partial(jnp.einsum, "...ri,...id->...rd",
                     precision=lax.Precision.HIGHEST)
    u = solved(inverse, v.astype(f32))
    k_in = solved(inverse * since[..., None, :], k.astype(f32)).astype(dtype)
    scores = (jnp.einsum("...rd,...id->...ri", q, k,
                         preferred_element_type=f32) * decay).astype(dtype)
    q_in = (q * since[..., None]).astype(dtype)
    k_out = (k * jnp.exp(G[..., -1:] - G)[..., None]).astype(dtype)
    kept = since[..., -1, None, None]                     # exp(G_C)

    def step(state, xs):
        u, k_in, scores, q_in, k_out, kept = xs
        s = state.astype(dtype)
        w = (u - jnp.einsum("bhrk,bhvk->bhrv", k_in, s,
                            preferred_element_type=f32)).astype(dtype)
        o = jnp.einsum("bhrk,bhvk->bhrv", q_in, s,
                       preferred_element_type=f32) \
            + jnp.einsum("bhri,bhiv->bhrv", scores, w,
                         preferred_element_type=f32)
        state = kept * state + jnp.einsum(
            "bhiv,bhik->bhvk", w, k_out, preferred_element_type=f32)
        return state, o.astype(dtype)

    state = jnp.zeros((B, H, v.shape[-1], q.shape[-1]), f32)
    _, o = lax.scan(step, state, (u, k_in, scores, q_in, k_out, kept))
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 2), 0, 1)        # (B, n, C, H, dv)
    return o.reshape(B, n * chunk, H, -1)[:, :T]


def _decay_rate_init(key, shape, dtype=jnp.float32):
    """``A_log``: the logarithm of a rate drawn uniformly from (0, 16),
    as the family's modules draw it."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _step_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias``: softplus's inverse of a step drawn log-uniformly
    from (0.001, 0.1), as the family's modules draw it."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class DeltaMixer(nn.Module):
    """A gated delta-rule recurrence in attention's place (Gated
    DeltaNet): per head held a state ``S (dv, dk)`` that each position
    decays, corrects along its key and reads with its query
    (``delta_step``).  Queries, keys and values come from three
    projections through a short causal convolution and a SiLU each;
    queries and keys are l2-normed a head (queries scaled by
    ``dk^-0.5``); the write strength ``beta`` and the log-decay ``g``
    from two projections of one number a head; the result is normed a
    head (one gain of ``dv``), gated by a SiLU of a sixth projection
    and projected back.  ``Wo`` has rows for the heads held alone.

    A whole window runs chunk-wise (``delta_scan``) from a state of
    nought: windows start at an episode's first position.  Positions
    past an episode's end (token -1) are computed like any other: they
    follow every real position and reach none, so their ``beta`` and
    ``g`` are left as the projections give them.  The one-token step
    carries the state and the convolutions' last ``taps - 1`` inputs."""
    sizes: Sizes

    @nn.compact
    def __call__(self, a, cache=None, pos=None, valid=None):
        z = self.sizes
        heads, _ = held_heads(z)
        dk, dv = z.delta_key_dim, z.delta_value_dim
        widths = (heads * dk, heads * dk, heads * dv)
        with jax.named_scope("net.delta.project"):
            x = jnp.concatenate([
                checkpoint_name(_project(a, width, name), "delta_" + name)
                for name, width in zip("qkv", widths)], -1)
            taps = jnp.concatenate([
                Kernel((z.conv_taps, width), name=name + "_conv")()
                for name, width in zip("qkv", widths)], -1)
            # four taps, the SiLU and the l2 norms in float32: one fused
            # pass over the projections' result
            x, taps = x.astype(jnp.float32), taps.astype(jnp.float32)
            if cache is None:
                mixed = short_conv(x, taps)
            else:
                state, before = cache
                mixed = short_conv(x[:, None], taps, before)[:, 0]
                before = jnp.concatenate(
                    [before[:, 1:], x[:, None].astype(before.dtype)], 1)
            mixed = jax.nn.silu(mixed)
            q, k, v = (part.reshape(part.shape[:-1] + (heads, -1))
                       for part in jnp.split(
                           mixed, np.cumsum(widths[:2]), -1))
            q, k = (part * lax.rsqrt(
                (part * part).sum(-1, keepdims=True) + L2_EPS)
                for part in (q, k))
            q, k, v = (part.astype(a.dtype)
                       for part in (q / math.sqrt(dk), k, v))
            # in (0, 2): a write may turn its key's direction over (the
            # negative-eigenvalue side, arXiv:2411.12537)
            # (the two projections of one number a head hand their sums
            # out in float32: a log-decay rounded to the compute dtype
            # is summed over a chunk and raised to a power)
            beta = 2.0 * jax.nn.sigmoid(_project(a, heads, "b", jnp.float32))
            rate = self.param("A_log", _decay_rate_init, (heads,))
            bias = self.param("dt_bias", _step_bias_init, (heads,))
            g = -jnp.exp(rate.astype(jnp.float32)) * jax.nn.softplus(
                _project(a, heads, "a", jnp.float32)
                + bias.astype(jnp.float32))
            gate = jax.nn.silu(checkpoint_name(
                _project(a, heads * dv, "g"), "delta_gate"))
        with jax.named_scope("net.delta.scan"):
            if cache is None:
                o = delta_scan(q, k, v, g, beta, z.delta_chunk)
            else:
                state, o = delta_step(state, q, k, v, g, beta)
                cache = (state, before)
        with jax.named_scope("net.delta.out"):
            o = rms_norm(o.astype(a.dtype), Scale(dv, name="o_norm")(), z.eps)
            o = o.reshape(gate.shape) * gate
            o = checkpoint_name(_project(o, z.hidden, "o"), "delta_out")
        # how much of its state a position keeps, over real positions
        kept = jnp.exp(g)
        if valid is None:
            return o, cache, kept.mean()
        return o, cache, (kept * valid[..., None]).sum() / jnp.maximum(
            valid.sum() * heads, 1)


def swiglu(x, w1, w3, w2):
    # the three products go by name (``KEPT_NAMES``); the hidden between
    # them is kept nowhere
    gate = checkpoint_name(jnp.dot(x, w1), "mlp_gate")
    up = checkpoint_name(jnp.dot(x, w3), "mlp_up")
    return checkpoint_name(jnp.dot(jax.nn.silu(gate) * up, w2), "mlp_out")


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


def dense_experts(m, here, weights, w1, w3, w2):
    """Every held expert over every position, its part weighted by the
    router's weight where it was picked (``here (N, k, held)``) and by
    nought where not: the held stack as ONE SwiGLU of width ``held *
    expert_width``, three dense products."""
    gate = jnp.where(here, weights[..., None], 0.0).sum(1)    # (N, held)
    h = (jax.nn.silu(jnp.einsum("nd,edf->nef", m, w1))
         * jnp.einsum("nd,edf->nef", m, w3))
    h = (h * gate[..., None]).astype(m.dtype)
    return jnp.einsum("nef,efd->nd", h, w2)


# the grouped products' tiles, read on the chip (``PERF.md`` section 6,
# PR 39): rows of picks a grid step, which the passes between the
# products take a turn as well (one expert layer alone, forward and
# backward, 4,438 live rows of 65,536: 15.2 ms at 512, 14.7 at 256 and
# 128 while gathers over all 65,536 rows stood beside the kernels;
# 1,024 run out of a kernel's 16 MB of fast memory; the passes by
# turns of 512 / 1,024 / 2,048 / 4,096 rows: 5.27 / 5.43 / 7.22 / 6.98
# ms, and 9.74 / 9.59 / 12.95 / 11.60 at 14,176 live rows), and the
# most of a product's contracted and output widths (at 512: 15.9 ms
# where 1,024 read 15.2; a width under it goes in whole: an expert's
# 768)
GROUPED_ROWS = 512
GROUPED_WIDTH = 1024


def _grouped_tiles(rows, d, f):
    """``{width: tile}`` for the grouped products over ``rows`` picks
    at residual width ``d`` and expert width ``f``, or None where the
    rows are not whole tiles or a width is not whole tiles of whole
    lanes."""
    tiles = {width: min(GROUPED_WIDTH, width) for width in (d, f)}
    if rows % GROUPED_ROWS or any(
            tile % LANES or width % tile for width, tile in tiles.items()):
        return None
    return tiles


def _sorted_picks(here, weights):
    """The picks ``here (N, k, held)`` in the order the grouped
    products read them: ``src (N * k,)``, the pick in each row of a
    buffer that holds the live picks first, grouped by expert, and
    everything else behind them; each row's router weight, which rides
    the sort; ``live (N, k)``."""
    held = here.shape[-1]
    live = here.any(-1)
    key = jnp.where(live, jnp.argmax(here, -1), held).reshape(-1)
    rows = jnp.arange(key.shape[0], dtype=jnp.int32)
    _, src, by_row = lax.sort(
        (key.astype(jnp.int32), rows, weights.reshape(-1)), num_keys=1)
    return src, by_row[:, None], live


def _to_picks(src, numbers):
    """One number a row of the sorted buffer, back in the picks' own
    order: a permutation's transpose is its inverse, and sorting by
    ``src`` is that inverse (sixteen times sooner on the chip than a
    gather of 65,536 scalars)."""
    return lax.sort((src, numbers), num_keys=1)[1]


def _products(tiles, interpret):
    """The library's grouped product and its transpose (``megablox``)
    at this module's tiles: ``product(lhs (rows, a), rhs (groups, a,
    b) or, ``transposed``, (groups, b, a))`` and ``outer(lhs (rows, a),
    rhs (rows, b)) -> (groups, a, b)``, each row with its group's
    matrix, float32 sums, rows past the groups' end never visited (a
    product's rows there hold whatever the memory held)."""
    # (the package's own ``gmm`` is these two under one tiling for both
    # directions; an expert's 768 against 2,048 takes one each)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    def product(lhs, rhs, sizes, dtype, transposed=False):
        a, b = lhs.shape[1], rhs.shape[1 if transposed else 2]
        return gmm(
            lhs, rhs, sizes, dtype, (GROUPED_ROWS, tiles[a], tiles[b]),
            transpose_rhs=transposed, interpret=interpret)

    def outer(lhs, rhs, sizes, dtype):
        a, b = lhs.shape[1], rhs.shape[1]
        return tgmm(
            lhs.swapaxes(0, 1), rhs, sizes, dtype,
            (GROUPED_ROWS, tiles[a], tiles[b]), interpret=interpret)

    return product, outer


def _live_chunks(count, body, carry):
    """``body(start, carry)`` over each chunk of ``GROUPED_ROWS`` rows
    of a sorted buffer that holds any of its first ``count`` rows, the
    live ones: as the kernels, time by tiles of live rows."""
    return lax.fori_loop(
        0, (count + GROUPED_ROWS - 1) // GROUPED_ROWS,
        lambda turn, carry: body(turn * GROUPED_ROWS, carry), carry)


def _row_by_row(count, fn, buffers, like):
    """``fn`` of the ``buffers``' live rows, chunk by chunk, as new
    buffers of ``like``'s ``(width, dtype)``s; the rows behind the live
    ones are left as the memory was (nothing reads them: a pass over
    the worst case's 65,536 rows, or a memset of them, costs 0.4-1.0 ms
    on the chip, sixteen times a layer)."""
    def body(start, out):
        made = fn(*(lax.dynamic_slice_in_dim(buffer, start, GROUPED_ROWS)
                    for buffer in buffers))
        return tuple(
            lax.dynamic_update_slice_in_dim(o, r.astype(o.dtype), start, 0)
            for o, r in zip(out, made))

    return _live_chunks(count, body, tuple(
        lax.empty((buffers[0].shape[0], width), dtype)
        for width, dtype in like))


def _spread(source, position, count):
    """``source (N, width)``'s row ``position[r]`` in each live row
    ``r`` of a buffer as long as ``position``."""
    return _row_by_row(count, lambda at: (source[at],), [position],
                       [(source.shape[1], source.dtype)])[0]


def _collect(buffers, position, count, N):
    """``_spread``'s transpose: the sum of the ``buffers``' live rows
    ``r`` into row ``position[r]`` of ``(N, width)``, in float32.  The
    chip adds a live row where it belongs in ~0.14 us, and gathers one
    of the worst case's 65,536 rows, live or not, in ~0.04."""
    width = buffers[0].shape[1]

    def body(start, out):
        rows = sum(lax.dynamic_slice_in_dim(
            buffer, start, GROUPED_ROWS).astype(jnp.float32)
            for buffer in buffers)
        live = (start + jnp.arange(GROUPED_ROWS) < count)[:, None]
        at = lax.dynamic_slice_in_dim(position, start, GROUPED_ROWS)
        return out.at[at].add(jnp.where(live, rows, 0))

    return _live_chunks(count, body, jnp.zeros((N, width), jnp.float32))


def _silu_and_slope(g):
    s = jax.nn.sigmoid(g)
    return g * s, s * (1 + g * (1 - s))


def _float32(fn):
    return lambda *rows: fn(*(r.astype(jnp.float32) for r in rows))


@partial(jax.custom_vjp, nondiff_argnums=(6,))
def _grouped(m, here, weights, w1, w3, w2, interpret):
    return _grouped_forward(m, here, weights, w1, w3, w2, interpret)[0]


def _grouped_forward(m, here, weights, w1, w3, w2, interpret):
    N, k = weights.shape
    d, f = w1.shape[1:]
    product, _ = _products(_grouped_tiles(N * k, d, f), interpret)
    sizes = here.sum((0, 1)).astype(jnp.int32)
    src, by_row, live = _sorted_picks(here, weights)
    position, count = src // k, sizes.sum()
    x = _spread(m, position, count)                      # (N * k, d)
    g1 = product(x, w1, sizes, m.dtype)
    g3 = product(x, w3, sizes, m.dtype)
    # as the dense path: the weight meets the hidden, in float32
    h, = _row_by_row(
        count, _float32(lambda g1, g3, w: (jax.nn.silu(g1) * g3 * w,)),
        [g1, g3, by_row], [(f, m.dtype)])
    y = product(h, w2, sizes, m.dtype)
    out = _collect([y], position, count, N).astype(m.dtype)
    return out, (x, g1, g3, h, by_row, w1, w3, w2, sizes, src, live)


def _grouped_backward(interpret, kept, ct):
    x, g1, g3, h, by_row, w1, w3, w2, sizes, src, live = kept
    N, k = live.shape
    f = h.shape[1]
    product, outer = _products(
        _grouped_tiles(src.shape[0], x.shape[1], f), interpret)
    position, count = src // k, sizes.sum()
    d_y = _spread(ct, position, count)
    d_h = product(d_y, w2, sizes, x.dtype, transposed=True)

    def back(d_h, g1, g3, w):
        silu, slope = _silu_and_slope(g1)
        return (d_h * w * g3 * slope, d_h * w * silu,
                (d_h * silu * g3).sum(-1, keepdims=True))

    d_g1, d_g3, d_by_row = _row_by_row(
        count, _float32(back), [d_h, g1, g3, by_row],
        [(f, x.dtype), (f, x.dtype), (1, by_row.dtype)])
    d_weights = jnp.where(
        live, _to_picks(src, d_by_row[:, 0]).reshape(live.shape), 0)
    d_m = _collect(
        [product(d_g1, w1, sizes, x.dtype, transposed=True),
         product(d_g3, w3, sizes, x.dtype, transposed=True)],
        position, count, N)
    return (d_m.astype(x.dtype), None, d_weights,
            outer(x, d_g1, sizes, w1.dtype), outer(x, d_g3, sizes, w3.dtype),
            outer(h, d_y, sizes, w2.dtype))


_grouped.defvjp(_grouped_forward, _grouped_backward)


def grouped_experts(m, here, weights, w1, w3, w2, interpret=False):
    """The same sum as ``dense_experts`` over the picks that fall on
    held experts ALONE.  The picks are sorted by expert, the live ones
    first (``_sorted_picks``), into buffers of the worst case's ``N *
    k`` rows of which only the live rows are ever written or read: the
    live picks' rows of ``m`` are spread there (``_spread``), three
    grouped products run over them whose kernels visit only tiles of
    live rows (``_products``), the hidden between them is made chunk by
    live chunk, and each live row's result is added to its position
    (``_collect``).  Coming back every step is its transpose, by hand
    (``_grouped_backward``).  No capacity, no pick dropped, and a time
    that turns on the live rows alone.  ``interpret`` runs the kernels'
    bodies as plain JAX (tier-1, on the CPU)."""
    # one dtype through the products, as the dense path's promotion
    m, w1, w3, w2 = (a.astype(jnp.result_type(m, w1, w3, w2))
                     for a in (m, w1, w3, w2))
    return _grouped(m, here, weights, w1, w3, w2, interpret)


def held_experts(m, selected, weights, kernels, sizes, valid=None):
    """What this chip's experts add: ``sum over selected e HELD HERE of
    w_e * SwiGLU_e(m)`` for ``m (N, d)``, and the positions routed to
    each held expert ``(experts_held,)``.  Rows that are not ``valid
    (N,)`` (padding past an episode's end, which reaches no loss term
    and no real position) take no expert.  No pick is dropped.

    Two paths, chosen where the program is lowered and by shapes
    alone.  For a TPU, over a whole window at lane-wide shapes:
    ``grouped_experts``, the arithmetic the picks need.  Everywhere
    else (a CPU, the actors' one-token step, the tiny presets):
    ``dense_experts``, sixteen times that arithmetic at sixteen held
    experts of which an eighth of the picks fall here, in a time that
    does not turn on the picks; it stays as the statement the grouped
    path is held to.  (Sorted picks through ``lax.ragged_dot`` between
    a gather and a scatter-add cost 180-234 ms a step: PR 33.)"""
    held = sizes.experts_held
    here = selected[..., None] == sizes.first_expert + jnp.arange(held)
    if valid is not None:
        here = here & valid[:, None, None]
    counts = here.sum((0, 1))
    operands = (m, here, weights) + tuple(kernels)
    d, f = kernels[0].shape[1:]
    if _grouped_tiles(weights.size, d, f) is None:
        return dense_experts(*operands), counts
    return lax.platform_dependent(
        *operands, default=dense_experts, tpu=grouped_experts), counts


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
    """One decoder layer: a mixer along the window (by ``kind``: a
    delta-rule recurrence, a latent attention, or grouped-query
    attention with or without a window) and an MLP (dense or sparse
    experts), each a residual branch normed going in and coming out as
    the net declares.  Beside the residual stream and the actor's cache
    it hands back what the layer counted: ``expert_load`` (positions
    routed to each held expert) or ``retention`` (a delta layer's mean
    decay), whichever it has."""
    sizes: Sizes
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, h, cache=None, pos=None, valid=None):
        z = self.sizes

        def normed(y, name, wanted):
            return RMSNorm(z.eps, name=name)(y) if wanted else y

        counted = {}
        a = normed(h, "pre_attn_norm", z.pre_norms)
        if self.kind == LINEAR:
            o, cache, counted["retention"] = DeltaMixer(z, name="delta")(
                a, cache, pos, valid)
        elif self.kind == LATENT:
            o, cache = LatentAttention(z, name="attn")(a, cache, pos)
        else:
            o, cache = Attention(z, self.kind, name="attn")(a, cache, pos)
        h = h + normed(o, "post_attn_norm", z.post_norms)
        m = normed(h, "pre_mlp_norm", z.pre_norms)
        if self.dense:
            with jax.named_scope("net.mlp"):
                y = SwiGLU(z.dense_width, name="mlp")(m)
        else:
            y, counted["expert_load"] = SparseExperts(z, name="moe")(m, valid)
        h = h + normed(y, "post_mlp_norm", z.post_norms)
        return h, cache, counted


# a layer over a whole window is made again coming back, all but what
# ``KEPT_NAMES`` names
RematLayer = nn.remat(
    Layer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT_NAMES))


class NextNext(nn.Module):
    """The next-next-token module over a whole window: at position
    ``t`` the trunk's last state and the embedding of the token at
    ``t + 1``, each normed, joined and projected back to the residual
    width, through one expert layer of the trunk's own form, normed:
    features that the model's own head turns into a second prediction,
    of the token at ``t + 2``.  ``following (B, T)`` is the window
    moved one position on (-1 where no token follows); the embedding
    and the head are the model's, shared."""
    sizes: Sizes

    @nn.compact
    def __call__(self, h, table, following):
        z = self.sizes
        with jax.named_scope("net.mtp"):
            joined = jnp.concatenate([
                RMSNorm(z.eps, name="state_norm")(h),
                RMSNorm(z.eps, name="token_norm")(
                    table[jnp.maximum(following, 0)])], -1)
            u = _project(joined, z.hidden, "join")
        # the layer's attention and experts lie under their own scopes
        u, _, counted = RematLayer(z, z.layer_types[-1], False, name="layer")(
            u, None, None, following >= 0)
        with jax.named_scope("net.mtp"):
            return RMSNorm(z.eps, name="final_norm")(u), counted


class SequencePolicyNet(nn.Module):
    sizes: Sizes

    @property
    def sequence_length(self):
        return self.sizes.sequence_length

    def _cache(self, kind):
        """What the actor's cache holds of one layer of ``kind``, by
        name: a delta layer's state and the last inputs of its
        convolutions; else, for each of ``sequence_length`` positions,
        each key-value head's key and value, or the latent and the one
        rotated key that every head's are made from."""
        z = self.sizes
        heads, kv_heads = held_heads(z)
        if kind == LINEAR:
            dk, dv = z.delta_key_dim, z.delta_value_dim
            return {"state": (heads, dv, dk),
                    "conv": (z.conv_taps - 1, heads * (2 * dk + dv))}
        positions = (z.sequence_length,)
        if kind == LATENT:
            return {"latent": positions + (z.latent_kv,),
                    "rope": positions + (z.rope_dim,)}
        return {"k": positions + (kv_heads, z.head_dim),
                "v": positions + (kv_heads, z.head_dim)}

    def _slots(self):
        """Each layer's ``(cache entries, place)``: layers whose caches
        go by the same names are stacked under them, in layer order."""
        seen, slots = {}, []
        for kind in self.sizes.layer_types:
            entries = self._cache(kind)
            place = seen.get(tuple(entries), 0)
            seen[tuple(entries)] = place + 1
            slots.append((entries, place))
        return slots

    def init_hidden(self, batch_shape=()):
        """The actor's cache, empty: the position to write next and
        every layer's entries (``_cache``), the layers of one kind
        stacked behind the batch axes."""
        lead = tuple(batch_shape)
        entries = [entry for kind in self.sizes.layer_types
                   for entry in self._cache(kind).items()]
        layers = Counter(name for name, _ in entries)
        hidden = {name: jnp.zeros(lead + (layers[name],) + shape, jnp.float32)
                  for name, shape in entries}
        return dict(hidden, pos=jnp.zeros(lead, jnp.int32))

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
        h = table[jnp.maximum(tokens, 0)]
        if z.embed_scale:
            # mup_enabled: the embedding is scaled by sqrt(hidden_size)
            h = h * jnp.asarray(math.sqrt(z.hidden), table.dtype)
        layer = RematLayer if whole else Layer
        pos = None if whole else hidden["pos"]
        slots = self._slots()
        caches, counted = [], []
        for i, (kind, (entries, place)) in enumerate(
                zip(z.layer_types, slots)):
            cache = None if whole else tuple(
                hidden[name][:, place] for name in entries)
            h, cache, c = layer(z, kind, i < z.dense_layers,
                                name=f"layer_{i}")(h, cache, pos, valid)
            counted.append(c)
            caches.append(cache)
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
        if z.nextn_modules and (whole or self.is_initializing()):
            # the module runs over whole windows only (an actor drafts
            # nothing); a net initialised through the one-token step
            # makes its parameters on windows of one position
            window = tokens if whole else tokens[:, None]
            drafted, c = NextNext(z, name="mtp")(
                h if whole else h[:, None], table, rows_on(window, 1))
            counted.append(c)
            if whole:
                out["mtp"] = FactoredPolicy(drafted, kernel)
        if whole:
            # what the layers counted, under the names the step's
            # counters go by (``ops.losses.sequence_counters``)
            out["counts"] = counts = {}
            loads = [c["expert_load"] for c in counted if "expert_load" in c]
            if loads:
                # positions routed to each held expert, by expert layer
                counts["expert_load"] = jnp.stack(loads)
                counts["expert_picks"] = (
                    valid.sum() * z.experts_per_token).astype(jnp.float32)
            kept = [c["retention"] for c in counted if "retention" in c]
            if kept:
                # the mean decay a position, over the delta layers
                counts["delta_retention"] = jnp.stack(kept).mean()
        else:
            stacked = {}
            for (entries, _), cache in zip(slots, caches):
                for name, entry in zip(entries, cache):
                    stacked.setdefault(name, []).append(entry)
            out["hidden"] = {"pos": pos + 1} | {
                name: jnp.stack(layers, 1).astype(hidden[name].dtype)
                for name, layers in stacked.items()}
        return out


def sequence_net(preset):
    """The module at a named size (``PRESETS``)."""
    if preset not in PRESETS:
        raise ValueError(
            f"no sequence-net preset {preset!r}; there are "
            f"{sorted(PRESETS)}")
    return SequencePolicyNet(PRESETS[preset])
