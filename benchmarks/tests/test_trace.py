"""The reduction from a profiler trace to numbers, on a small trace
recorded on a TPU v5e (30 calls of one jitted step, a 20 ms host block
after every tenth)."""

import json
import os

import pytest

from benchmarks.harness import trace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "small_trace.json")) as f:
        raw = json.load(f)
    for plane in raw["planes"]:
        for line in plane["lines"]:
            line["events"] = [tuple(e) for e in line["events"]]
    return raw


def test_known_busy_idle_and_kernel_numbers(recorded):
    r = trace.reduce(recorded)
    # hand-worked from the listing: 30 jit_step modules, 1,434,840 ns,
    # less the 4 + 2 ns by which the first and last stick out of the
    # window of their own operations
    count, seconds = r["modules"]["jit_step"]
    assert count == 30
    assert seconds == pytest.approx(1434834e-9, rel=1e-9)
    # no bench:window span: first op start to last op end, 49,579,504 ns
    assert r["window_s"] == pytest.approx(49579504e-9, rel=1e-9)
    # ops never overlap here: their durations sum to 1,434,630 ns
    assert r["busy_s"] == pytest.approx(1434630e-9, rel=1e-6)
    assert r["chips"] == 1
    top, top_s = r["device_ops"][0]
    assert top == "tanh_add_fusion bf16[2048,7,11,32]"
    assert top_s == pytest.approx(30 * 47.6e-6, rel=0.01)
    # the two long gaps (21.55 and 21.02 ms) are the host's blocks
    assert [name for name, _ in r["idle_gaps"][:2]] == ["block", "block"]
    assert r["idle_gaps"][0][1] == pytest.approx(21554034e-9, rel=1e-3)


def test_window_span_clips_and_uncovered_gaps_get_the_given_name(recorded):
    mods = sorted(recorded["planes"][0]["lines"][0]["events"],
                  key=lambda e: e[1])
    lo = mods[0][1] - 1e6               # 1 ms before the first module
    hi = mods[9][1] + mods[9][2] + 2e6  # 2 ms into the block after the tenth
    cut = {"planes": [recorded["planes"][0], {
        "name": trace.HOST_PLANE, "lines": [{"name": "main", "events": [
            (trace.WINDOW_SPAN, lo, hi - lo)]}]}]}
    r = trace.reduce(cut, uncovered="idle_at_cap")
    assert r["modules"]["jit_step"][0] == 10
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert {name for name, _ in r["idle_gaps"]} == {"idle_at_cap"}
    assert max(s for _, s in r["idle_gaps"]) == pytest.approx(2e-3, rel=1e-6)


def test_labels():
    assert trace.op_label(
        "%copy.288 = u8[256,8,4,7,11,17]{5,4,3,2,1,0} copy(%x)"
    ) == "copy.288 u8[256,8,4,7,11,17]"
    assert trace.module_label("jit_step(1142258)") == "jit_step"
