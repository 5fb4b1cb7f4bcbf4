"""Counts of one fused step that a configuration brings with it: a
configuration that names ``cost: <module>`` is counted by
``benchmarks/cost/<module>.py``'s

    step_cost(param_shapes, train_args, geometry, ring_row_bytes)
        -> {"flops": float, "bytes": float}

(``geometry`` is the configuration's ``roofline`` section, whatever the
module wants in it) in place of ``harness/roofline.py``'s conv/dense
count: the operations the algorithm needs, recomputed ones not counted.
The share of the chip's peaks is still worked out by
``harness/roofline.py::roofline``."""
