"""Where ``trinity.fed``'s window lies, by the files' own numbers (PR 43).

The cell's rate follows its routers (``PERF.md`` section 6, PR 43), so
WHICH steps a window holds is part of what it measures.  Counts only,
no chip: the window and the server's epoch boundaries from the traffic
mix, the configuration's ``train_args`` and ``BENCHMARK.json``; and, in
one rehearsal at the net's tiny preset (``test_trinity_cell.py``'s),
that ``warm_steps`` of the traffic mix is what ``run.py`` waits for
before it opens the window.

    python benchmarks/tests/test_trinity_window.py <tiny config>

is that rehearsal's own process (a run ends in ``os._exit``).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_trinity_cell as tiny                            # noqa: E402
from test_trinity_cell import tiny_config                   # noqa: E402,F401

# the slowest the cell's step has read on the chip (0.60-0.65 s: ledger,
# PR 33; 0.27-0.33 since PR 39) and twice the longest an edge has waited
# for the device (4.5 s: my chip runs, PR 43): upper ends, so that the
# count below is the most the window can have received
STEP_SECONDS_AT_MOST = 0.65
EDGE_WAIT_SECONDS_AT_MOST = 10.0
WARM_STEPS = 5      # the rehearsal's: two more than the check's three


def _files():
    from benchmarks import run
    from benchmarks.harness.cells import Cell, load_manifest

    manifest = load_manifest()
    cell = Cell(manifest, tiny.CELL)
    return (cell.traffic, cell.config["train_args"],
            manifest["run_seconds"], run)


def _received_at_most(traffic, seconds):
    """Episodes the control plane has taken when the window closes: the
    warm offers, then ``rate_eps`` through the warm steps, both edges'
    waits and the window (priming goes past ``episodes_received``)."""
    waited = (traffic["warm_steps"] * STEP_SECONDS_AT_MOST
              + 2 * EDGE_WAIT_SECONDS_AT_MOST + seconds)
    return traffic["warm_offers"] + waited * traffic["rate_eps"]


def test_the_window_cannot_hold_an_epoch_boundary():
    traffic, train, seconds, _run = _files()
    first = train["minimum_episodes"] + train["update_episodes"]
    assert _received_at_most(traffic, seconds) < first


@pytest.mark.parametrize("what", ["holds_the_first_boundary",
                                  "ends_before_the_second",
                                  "does_not_wrap_the_ring"])
def test_the_traced_stretch(what):
    """A traced run's stretch after the window is one ``update_episodes``
    of offers long: it holds the server's first boundary whole, ends
    before the second, and the ring has a slot for every episode."""
    traffic, train, seconds, run = _files()
    stretch = run.TRACE_EPOCHS * train["update_episodes"]     # episodes
    first = train["minimum_episodes"] + train["update_episodes"]
    at_least = traffic["warm_offers"] + seconds * traffic["rate_eps"]
    at_most = _received_at_most(traffic, seconds) + stretch
    if what == "holds_the_first_boundary":
        assert at_least + stretch > first
    elif what == "ends_before_the_second":
        assert at_most < first + train["update_episodes"]
    else:
        assert (train["minimum_episodes"] + at_most
                < train["maximum_episodes"])


def rehearse(config_path):
    """``test_trinity_cell.rehearse`` with ``WARM_STEPS`` warm steps and
    each edge's step count printed."""
    from benchmarks.harness.probes import Probes

    request_edge = Probes.request_edge

    def said(self, *args, **kwargs):
        at, steps = request_edge(self, *args, **kwargs)
        print(f"edge closed after fused step {steps}", flush=True)
        return at, steps

    Probes.request_edge = said
    tiny.REHEARSAL["traffic"]["warm_steps"] = WARM_STEPS
    return tiny.rehearse(config_path)


def test_the_window_opens_after_the_traffic_files_warm_steps(tiny_config):
    """The window's opening edge is the first step boundary after
    ``warm_steps`` fused steps (the rehearsal's cap lets three through
    an epoch, so the edge may come up to three steps later)."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), str(tiny_config)],
        capture_output=True, text=True, timeout=1500, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is True
    edges = [int(l.split()[-1]) for l in lines
             if l.startswith("edge closed after fused step")]
    assert len(edges) == 2 and edges[0] < edges[1], lines[-30:]
    assert WARM_STEPS <= edges[0] <= WARM_STEPS + 3, edges


if __name__ == "__main__":
    os._exit(rehearse(*sys.argv[1:]))
