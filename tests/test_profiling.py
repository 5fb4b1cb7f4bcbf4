"""utils.profiling: the TraceWindow step-window state machine.

The window drives ``jax.profiler`` start/stop from the update-step
count; the tests stub the profiler (monkeypatched module attribute) so
the semantics — start at ``start_step``, stop at ``stop_step``,
one-shot, close-while-active flush, inactive with no ``trace_dir`` —
are asserted without touching a real trace backend."""

import jax
import pytest

from handyrl_tpu.utils.profiling import SectionTimers, TraceWindow


class _StubProfiler:
    class ProfileOptions:
        python_tracer_level = None
        enable_hlo_proto = None

    def __init__(self):
        self.calls = []
        self.options = []

    def start_trace(self, trace_dir, profiler_options=None):
        self.calls.append(("start", trace_dir))
        self.options.append(profiler_options)

    def stop_trace(self):
        self.calls.append(("stop", None))


@pytest.fixture()
def profiler(monkeypatch):
    stub = _StubProfiler()
    monkeypatch.setattr(jax, "profiler", stub)
    return stub


def test_window_starts_and_stops_at_configured_steps(profiler):
    win = TraceWindow("/tmp/tw", start_step=3, stop_step=5)
    for _ in range(2):
        win.tick()
    assert profiler.calls == [] and not win.active
    win.tick()                       # step 3: start fires
    assert profiler.calls == [("start", "/tmp/tw")]
    assert win.active and not win.done
    win.tick()                       # step 4: inside the window
    assert len(profiler.calls) == 1
    win.tick()                       # step 5: stop fires, one-shot
    assert profiler.calls[-1] == ("stop", None)
    assert win.done and not win.active


def test_window_traces_without_python_tracer_and_hlo_proto(profiler):
    """JAX's default Python tracer slows the trainer thread severalfold
    and the HLO proto slows the DRC step on the device (PERF.md, PR 24):
    the program's own trace is taken with both off."""
    win = TraceWindow("/tmp/tw", start_step=1, stop_step=2)
    win.tick()
    (options,) = profiler.options
    assert options.python_tracer_level == 0
    assert options.enable_hlo_proto is False


def test_a_trace_that_cannot_be_reduced_says_so_and_goes_on(
        profiler, capsys):
    """The stub wrote no xplane: the reduction prints one line, leaves
    no step_phases.json, and the window still ends as done."""
    win = TraceWindow("/tmp/tw-none", start_step=1, stop_step=2,
                      hlo_text=lambda: "")
    win.tick()
    win.tick()
    out = capsys.readouterr().out
    assert "profiler trace not reduced" in out
    assert "step phases =" not in out
    assert win.done and not win.active


def test_window_is_one_shot_after_stop(profiler):
    win = TraceWindow("/tmp/tw", start_step=1, stop_step=2)
    for _ in range(6):
        win.tick()
    # exactly one start/stop pair no matter how many later ticks
    assert profiler.calls == [("start", "/tmp/tw"), ("stop", None)]
    assert win.step == 2             # done windows stop counting


def test_close_while_active_stops_the_trace(profiler):
    win = TraceWindow("/tmp/tw", start_step=1, stop_step=10)
    win.tick()
    assert win.active
    win.close()                      # early shutdown mid-window
    assert profiler.calls == [("start", "/tmp/tw"), ("stop", None)]
    assert win.done and not win.active
    win.tick()                       # and it stays closed
    assert len(profiler.calls) == 2


def test_close_when_never_started_is_a_noop(profiler):
    win = TraceWindow("/tmp/tw", start_step=5, stop_step=6)
    win.tick()
    win.close()
    assert profiler.calls == []
    assert not win.active


def test_empty_trace_dir_disables_the_window(profiler):
    win = TraceWindow("", start_step=1, stop_step=2)
    for _ in range(4):
        win.tick()
    win.close()
    assert profiler.calls == []
    assert win.done and win.step == 0


def test_section_timers_accumulate_and_reset():
    timers = SectionTimers()
    with timers.section("update"):
        pass
    with timers.section("update"):
        pass
    snap = timers.snapshot()
    assert snap["update"]["n"] == 2
    assert snap["update"]["sec"] >= 0.0
    # snapshot(reset=True) is the default: the next epoch starts clean
    assert timers.snapshot() == {}
