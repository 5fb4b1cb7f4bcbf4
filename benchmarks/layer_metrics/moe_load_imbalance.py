"""Positions routed to the fullest held expert of any expert layer over the mean of all held experts, from the counters of Trainer.step_profile()'s 16 steps (program_counter); 1.0 is an even load."""
from benchmarks.harness.sequence_parts import moe_load_imbalance as read  # noqa: F401
