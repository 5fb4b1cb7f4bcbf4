"""Operations and bytes of one fused step of the sparse-expert sequence
policy (``trinity_mini_ep8``), counted from shapes: what the algorithm
needs, recomputed operations not counted.

A trained position costs three forward units (forward, and the two
products of the backward pass).  Forward operations of one position:

  attention   the five projections of every layer (q, k, v, o and the
              output gate), and scores and weighted values over the
              keys VISIBLE to it: ``4 * heads * head_dim`` a key; a
              window layer's position t sees ``min(t + 1, window)`` keys,
              a full layer's ``t + 1``
  mlp         the dense layers' SwiGLU
  moe         the router over all its experts, the shared expert, and
              ``experts_per_token * held / experts`` held picks a
              position by expectation (one, at 8 * 16 / 128)
  head        the policy head over the held vocabulary, the value head

Bytes: the float32 parameters with gradient and Adam's two moments read
and written once (32 a parameter, as ``harness/roofline.py`` counts
them), each layer's outputs written once going forward and read once
coming back in the compute dtype, the ring's rows.  ``parts`` splits
both by part for the per-part readers; an embedding row is read, not
multiplied.
"""

import math

import jax

PARTS = ("attention", "mlp", "moe", "head")


def _size(tree):
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))


def visible_keys(kind, steps, window):
    """Mean number of keys a position of a ``steps``-long window sees."""
    if kind == "sliding_attention":
        return sum(min(t + 1, window) for t in range(steps)) / steps
    return (steps + 1) / 2


def step_cost(param_shapes, train_args, geometry, ring_row_bytes):
    """``geometry``: the configuration's ``roofline`` section
    (``layer_types`` of the layers held, ``sliding_window``,
    ``experts_per_token``, ``experts``)."""
    steps = train_args["forward_steps"]
    positions = train_args["batch_size"] * steps
    act = 2 if train_args.get("compute_dtype") == "bfloat16" else 4
    flops = dict.fromkeys(PARTS, 0.0)
    params = dict.fromkeys(PARTS, 0)
    outputs = dict.fromkeys(PARTS, 0)      # elements written a position
    for i, kind in enumerate(geometry["layer_types"]):
        layer = param_shapes[f"layer_{i}"]
        attn = layer["attn"]
        qk = attn["q"]["kernel"].shape[1]            # heads * head_dim
        flops["attention"] += 2 * sum(
            math.prod(attn[k]["kernel"].shape)
            for k in ("q", "k", "v", "o", "gate"))
        flops["attention"] += 4 * qk * visible_keys(
            kind, steps, geometry["sliding_window"])
        params["attention"] += _size(attn)
        outputs["attention"] += sum(
            attn[k]["kernel"].shape[1] for k in ("q", "k", "v", "o", "gate"))
        if "mlp" in layer:
            flops["mlp"] += 2 * _size(layer["mlp"])
            params["mlp"] += _size(layer["mlp"])
            outputs["mlp"] += sum(
                layer["mlp"][k]["kernel"].shape[1] for k in ("w1", "w3", "w2"))
        else:
            moe = layer["moe"]
            held = moe["experts"]["w1"]["kernel"].shape[0]
            picks = geometry["experts_per_token"] * held / geometry["experts"]
            one = _size(moe["experts"]) / held
            flops["moe"] += 2 * (_size(moe["router"]) + _size(moe["shared"])
                                 + picks * one)
            params["moe"] += _size(moe)
            outputs["moe"] += geometry["experts"] + (1 + picks) * sum(
                moe["shared"][k]["kernel"].shape[1]
                for k in ("w1", "w3", "w2"))
    head = {k: param_shapes[k] for k in ("head", "value_head", "final_norm")}
    flops["head"] = 2.0 * (_size(param_shapes["head"])
                           + _size(param_shapes["value_head"]))
    params["head"] = _size(head) + _size(param_shapes["embedding"])
    outputs["head"] = param_shapes["head"]["kernel"].shape[1]
    parts = {}
    for part in PARTS:
        parts[part] = {
            "flops": 3.0 * positions * flops[part],
            "bytes": 32.0 * params[part]
            + 2.0 * positions * outputs[part] * act}
    n_params = _size(param_shapes)
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": (sum(p["bytes"] for p in parts.values())
                  + 32.0 * (n_params - sum(params.values()))
                  + positions * ring_row_bytes),
        "parts": parts,
    }
