"""Share of the window's positions that carry the next-next-token module's term (a real token one and two rows on: an episode's last two rows and all padding carry none), from the counters of Trainer.step_profile()'s 16 steps (program_counter)."""
from benchmarks.harness.latent_parts import mtp_target_share as read  # noqa: F401
