"""Operations and bytes of one fused step of the delta-rule hybrid
sequence policy (``olmo_hybrid_tp2``), counted from shapes: what the
algorithm needs, recomputed operations not counted.

A trained position costs three forward units (forward, and the two
products of the backward pass).  Forward operations of one position:

  delta       what a delta layer's mixer does OUTSIDE its recurrence:
              the seven projections (q, k, v, the two of one number a
              head, the gate's, o) and the three short convolutions
              (``2 * taps`` a channel)
  delta_scan  the recurrence as the ALGORITHM needs it, whatever
              implements it: a position and head ``S k``, the rank-one
              update and ``S q``, ``3 * 2 * dk * dv``.  A chunk-wise
              pass does more arithmetic than this and a fused kernel
              may do less traffic: both are read against the same work,
              so the count knows no chunk
  attention   the four projections of a full-attention layer, and
              scores and weighted values over the ``(T + 1) / 2`` keys
              a position sees: ``4 * heads held * head_dim`` a key
  mlp         every layer's SwiGLU
  head        the policy head over the held vocabulary, the value head

Bytes as ``cost/trinity.py`` counts them (32 a parameter; each layer's
outputs written once going forward and read once coming back in the
compute dtype; the ring's rows), but ``delta_scan``: ``q, k, v, beta,
g`` read and ``o`` written once going forward and once coming back in
the compute dtype, and no state through HBM.  The parts are disjoint:
the step's total counts nothing twice.
"""

import math

from .trinity import _size

PARTS = ("delta", "delta_scan", "attention", "mlp", "head")
SWIGLU = ("w1", "w3", "w2")


def step_cost(param_shapes, train_args, geometry, ring_row_bytes):
    """``geometry``: the configuration's ``roofline`` section
    (``layer_types`` of the layers held, ``heads_held``)."""
    steps = train_args["forward_steps"]
    positions = train_args["batch_size"] * steps
    act = 2 if train_args.get("compute_dtype") == "bfloat16" else 4
    heads = geometry["heads_held"]
    flops = dict.fromkeys(PARTS, 0.0)
    params = dict.fromkeys(PARTS, 0)
    outputs = dict.fromkeys(PARTS, 0)      # elements written a position
    scan_bytes = 0.0
    for i, kind in enumerate(geometry["layer_types"]):
        layer = param_shapes[f"layer_{i}"]
        if kind == "linear_attention":
            mixer = layer["delta"]
            projections = [mixer[k]["kernel"].shape for k in "qkvabgo"]
            convs = [mixer[k + "_conv"]["kernel"].shape for k in "qkv"]
            flops["delta"] += 2 * sum(map(math.prod, projections + convs))
            params["delta"] += _size(mixer)
            outputs["delta"] += sum(shape[1] for shape in projections + convs)
            dk = mixer["q"]["kernel"].shape[1] // heads
            dv = mixer["v"]["kernel"].shape[1] // heads
            flops["delta_scan"] += 3 * 2 * heads * dk * dv
            # q, k, v, o a head, and beta and g: forward and backward
            scan_bytes += 2.0 * positions * heads * (
                2 * dk + 2 * dv + 2) * act
        else:
            attn = layer["attn"]
            projections = [attn[k]["kernel"].shape for k in "qkvo"]
            flops["attention"] += 2 * sum(map(math.prod, projections))
            flops["attention"] += 4 * projections[0][1] * (steps + 1) / 2
            params["attention"] += _size(attn)
            outputs["attention"] += sum(shape[1] for shape in projections)
        flops["mlp"] += 2 * _size(layer["mlp"])
        params["mlp"] += _size(layer["mlp"])
        outputs["mlp"] += sum(
            layer["mlp"][k]["kernel"].shape[1] for k in SWIGLU)
    head = param_shapes["head"]
    flops["head"] = 2.0 * (_size(head) + _size(param_shapes["value_head"]))
    params["head"] = sum(_size(param_shapes[k]) for k in (
        "head", "value_head", "final_norm", "embedding"))
    outputs["head"] = head["kernel"].shape[1]
    parts = {part: {"flops": 3.0 * positions * flops[part],
                    "bytes": 32.0 * params[part]
                    + 2.0 * positions * outputs[part] * act}
             for part in PARTS}
    parts["delta_scan"]["bytes"] = scan_bytes
    n_params = _size(param_shapes)
    return {
        "flops": sum(p["flops"] for p in parts.values()),
        "bytes": (sum(p["bytes"] for p in parts.values())
                  + 32.0 * (n_params - sum(params.values()))
                  + positions * ring_row_bytes),
        "parts": parts,
    }
