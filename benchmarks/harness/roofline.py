"""What one fused step needs, counted from shapes, and the chip's
peaks.  Kept with the benchmark so that no later PR can recount.

The count below is the default, for nets of conv and dense layers that
apply each kernel to every sample.  A configuration whose net it does
not describe (an embedding table, stacked experts of which a token
meets a few, attention's score work) names ``cost: <module>`` and
brings ``benchmarks/cost/<module>.py`` as a file of its own, found by
that name as a plain reference is (``cost_function``).  ``peaks`` and
``roofline`` stay the one place that knows the chip.

Operations: 2 * cells * kh * kw * cin * cout per conv application and
2 * din * dout per dense one (the arithmetic of ``bench.py``'s
``model_flops_per_sample``, which is right for a net that applies each
kernel once and wrong for the DRC body, whose cells run ``repeats``
times a step: the configuration's ``kernel_repeats`` says so).  A
trained step costs forward + backward = 3 forwards; a burn-in step is
forward only.  Recomputed operations do not count.

Bytes: the ring rows the gather must read, the float32 parameters with
Adam's two moments and the gradient read and written once, and every
layer's activation written once going forward and read once coming
back, in the compute dtype.  Transient gradients of activations are
not counted: a kernel may keep them on the chip.
"""

import math

import jax

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 819 GB/s HBM, per chip
    "TPU v5 lite": {"flops": 197e12, "bytes": 819e9},
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; add a "
            f"row with its source to benchmarks/harness/roofline.py")
    return PEAKS[device_kind]


def _repeat(path, repeats):
    names = [getattr(k, "key", str(k)) for k in path]
    return next((int(r) for name, r in (repeats or {}).items()
                 if name in names), 1)


def forward_counts(param_shapes, cells, repeats=None):
    """(operations, layer-output elements) of ONE sample's forward."""
    flops = elements = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        shape, times = tuple(leaf.shape), _repeat(path, repeats)
        if len(shape) == 4:
            kh, kw, cin, cout = shape
            flops += times * 2 * cells * kh * kw * cin * cout
            elements += times * cells * cout
        elif len(shape) == 2:
            flops += times * 2 * shape[0] * shape[1]
            elements += times * shape[1]
    return flops, elements


def step_cost(param_shapes, train_args, geometry, ring_row_bytes):
    """Operations and bytes of one fused step.  ``geometry``: the
    configuration's ``board_cells``, ``seats_in_batch`` (rows the
    forward sees per drawn window and step) and ``kernel_repeats``."""
    fwd, elements = forward_counts(
        param_shapes, geometry["board_cells"],
        geometry.get("kernel_repeats"))
    rows = train_args["batch_size"] * geometry["seats_in_batch"]
    trained = train_args["forward_steps"]
    burn = train_args.get("burn_in_steps", 0) or 0
    n_params = sum(math.prod(l.shape) for l in jax.tree.leaves(param_shapes))
    act_bytes = 2 if train_args.get("compute_dtype") == "bfloat16" else 4
    flops = rows * fwd * (3 * trained + burn)
    bytes_ = (train_args["batch_size"] * (trained + burn) * ring_row_bytes
              + 32 * n_params
              + rows * elements * act_bytes * (2 * trained + burn))
    return {"flops": float(flops), "bytes": float(bytes_)}


def cost_function(config):
    """The configuration's count of one fused step:
    ``benchmarks/cost/<config["cost"]>.py``'s ``step_cost(param_shapes,
    train_args, geometry, ring_row_bytes) -> {"flops", "bytes"}`` where
    the configuration names one, else ``step_cost`` above."""
    if "cost" not in config:
        return step_cost
    import importlib

    return importlib.import_module(
        "benchmarks.cost." + config["cost"]).step_cost


def roofline(cost, device_kind, seconds, chips=1):
    """(share in %, which bound) of the least time the chips could
    take over the time they took."""
    peak = peaks(device_kind)
    t_flops = cost["flops"] / (chips * peak["flops"])
    t_bytes = cost["bytes"] / (chips * peak["bytes"])
    least = max(t_flops, t_bytes)
    return 100.0 * least / seconds, ("compute" if t_flops >= t_bytes
                                     else "memory")
