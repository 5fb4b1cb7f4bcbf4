"""Share of the window's positions that hold a token (the rest is padding past an episode's end, computed all the same), from the counters of Trainer.step_profile()'s 16 steps (program_counter)."""
from benchmarks.harness.sequence_parts import seq_fill_share as read  # noqa: F401
