"""Operations and bytes of one fused step of the latent-attention
sequence policy with its next-next-token module (``joyai_flash_ep16``),
counted from shapes: what the algorithm needs.  Recomputed operations
are not counted, nor the zeros a kernel pads a head with.

A trained position costs three forward units (forward, and the two
products of the backward pass).  Forward operations of one position:

  attention   the five latent projections (q_a, q_b, kv_a, kv_b, o) of
              every layer held, the module's layer included, and scores
              and weighted values over the keys VISIBLE to it:
              ``2 * heads * (query-key head + value head)`` a key
              (2 * 32 * (192 + 128)), ``(T + 1) / 2`` keys a position,
              every layer a full causal one
  mlp         the dense layer's SwiGLU
  moe         in every expert layer, the module's included: the router
              over all its experts, the shared expert, and
              ``experts_per_token * held / experts`` held picks a
              position by expectation (a half, at 8 * 16 / 256)
  head        the policy head over the held vocabulary, the value head
  mtp         what is the module's alone: the join of state and token
              (4096 x 2048) and its own pass through the model's head

Bytes as ``cost/trinity.py`` counts them: 32 a parameter (the head's
kernel and the embedding once, under ``head``), each layer's outputs
written once going forward and read once coming back in the compute
dtype, the ring's rows.
"""

import math

from .trinity import _size

PARTS = ("attention", "mlp", "moe", "head", "mtp")
PROJECTIONS = ("q_a", "q_b", "kv_a", "kv_b", "o")
SWIGLU = ("w1", "w3", "w2")


def step_cost(param_shapes, train_args, geometry, ring_row_bytes):
    """``geometry``: the configuration's ``roofline`` section
    (``num_hidden_layers`` held, ``experts_per_token``, ``experts``)."""
    steps = train_args["forward_steps"]
    positions = train_args["batch_size"] * steps
    act = 2 if train_args.get("compute_dtype") == "bfloat16" else 4
    flops = dict.fromkeys(PARTS, 0.0)
    params = dict.fromkeys(PARTS, 0)
    outputs = dict.fromkeys(PARTS, 0)      # elements written a position
    module = param_shapes["mtp"]
    layers = [param_shapes[f"layer_{i}"]
              for i in range(geometry["num_hidden_layers"])]
    for layer in layers + [module["layer"]]:
        attn = layer["attn"]
        widths = [attn[k]["kernel"].shape for k in PROJECTIONS]
        flops["attention"] += 2 * sum(map(math.prod, widths))
        # a visible key meets every head's query-key part (q_b's
        # columns) and gives every head's value part (o's rows)
        flops["attention"] += 2 * (
            attn["q_b"]["kernel"].shape[1] + attn["o"]["kernel"].shape[0]
        ) * (steps + 1) / 2
        params["attention"] += _size(attn)
        outputs["attention"] += sum(shape[1] for shape in widths)
        if "mlp" in layer:
            flops["mlp"] += 2 * _size(layer["mlp"])
            params["mlp"] += _size(layer["mlp"])
            outputs["mlp"] += sum(
                layer["mlp"][k]["kernel"].shape[1] for k in SWIGLU)
        else:
            moe = layer["moe"]
            held = moe["experts"]["w1"]["kernel"].shape[0]
            picks = geometry["experts_per_token"] * held / geometry["experts"]
            one = _size(moe["experts"]) / held
            flops["moe"] += 2 * (_size(moe["router"]) + _size(moe["shared"])
                                 + picks * one)
            params["moe"] += _size(moe)
            outputs["moe"] += geometry["experts"] + (1 + picks) * sum(
                moe["shared"][k]["kernel"].shape[1] for k in SWIGLU)
    head = param_shapes["head"]
    flops["head"] = 2.0 * (_size(head) + _size(param_shapes["value_head"]))
    params["head"] = sum(_size(param_shapes[k]) for k in (
        "head", "value_head", "final_norm", "embedding"))
    outputs["head"] = head["kernel"].shape[1]
    flops["mtp"] = 2.0 * (_size(module["join"]) + _size(head))
    params["mtp"] = _size(module) - _size(module["layer"])
    outputs["mtp"] = (module["join"]["kernel"].shape[1]
                      + head["kernel"].shape[1])
    parts = {part: {"flops": 3.0 * positions * flops[part],
                    "bytes": 32.0 * params[part]
                    + 2.0 * positions * outputs[part] * act}
             for part in PARTS}
    n_params = _size(param_shapes)
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": (sum(p["bytes"] for p in parts.values())
                  + 32.0 * (n_params - sum(params.values()))
                  + positions * ring_row_bytes),
        "parts": parts,
    }
