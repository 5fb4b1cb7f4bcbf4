"""Share of an expert layer's picks (positions that hold a token x experts per token) that fell on the experts this chip holds, mean over the expert layers, from the counters of Trainer.step_profile()'s 16 steps (program_counter): how much expert work a seed's router gave this chip (12.5% / 6.25% by expectation at 16 of 128 / 256 held), which the held experts' time follows where they run as grouped products."""
from benchmarks.harness.program_spans import _reader
from benchmarks.harness.sequence_parts import _counter


@_reader
def moe_held_pick_share(run):
    share = _counter(run, "held_pick_share")
    return None if share is None else 100.0 * share


read = moe_held_pick_share
