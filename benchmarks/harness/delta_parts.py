"""Readers of what a net with delta-rule layers says of them:
``Trainer.step_profile()``'s ``scopes`` under ``net.delta.*`` and
``net.mlp``, and the counter ``delta_retention``.  As
``sequence_parts.py``'s readers, whose helpers these use: none raises;
where the program has no such scope or counter (another net, or a
program older than they are) the reader returns None and the line
leaves the metric out."""

from . import roofline as _roofline
from .program_spans import _reader
from .sequence_parts import _counter, _part_roofline, _scopes_ms

SCAN = ("net.delta.scan",)
DELTA = ("net.delta.project",) + SCAN + ("net.delta.out",)
MLP = ("net.mlp",)


@_reader
def step_delta_ms(run):
    return _scopes_ms(run, DELTA)


@_reader
def step_delta_scan_ms(run):
    return _scopes_ms(run, SCAN)


@_reader
def delta_scan_roofline(run):
    return _part_roofline(run, "delta_scan", SCAN)


@_reader
def delta_roofline(run):
    """The whole mixer: the recurrence's count and the rest's, over
    the three scopes' time."""
    ms = _scopes_ms(run, DELTA)
    parts = run.step_cost.get("parts") or {}
    if not ms or not {"delta", "delta_scan"} <= set(parts):
        return None
    cost = {key: parts["delta"][key] + parts["delta_scan"][key]
            for key in ("flops", "bytes")}
    share, bound = _roofline.roofline(
        cost, run.device["kind"], ms * 1e-3, chips=run.chips)
    run.notes["delta_roofline_bound"] = bound
    return share


@_reader
def step_mlp_ms(run):
    return _scopes_ms(run, MLP)


@_reader
def delta_retention(run):
    """A diagnostic of the weights' draw (how long the state
    remembers), not of speed: the step costs the same whatever it
    reads."""
    return _counter(run, "delta_retention")
