"""Readers of what a sequence net's step says of its own parts:
``Trainer.step_profile()``'s ``scopes`` (ms a step by the net's named
scopes) and ``counters`` (what the profiled steps counted).  As the
readers of ``program_spans.py``: none raises; where the program has no
such scope or counter (a board net, or a program older than they are)
the reader returns None and the line leaves the metric out."""

from . import roofline as _roofline
from .program_spans import _reader

ATTENTION = ("net.attention.window", "net.attention.full")
MOE = ("net.moe.route", "net.moe.experts", "net.moe.shared")
HEAD = ("net.head",)


def _scopes_ms(run, names):
    profile = run.probes.trainer.step_profile() or {}
    scopes = profile.get("scopes") or {}
    found = [scopes[name] for name in names if name in scopes]
    return sum(found) if found else None


def _counter(run, name):
    profile = run.probes.trainer.step_profile() or {}
    return (profile.get("counters") or {}).get(name)


def _part_roofline(run, part, names):
    ms = _scopes_ms(run, names)
    cost = (run.step_cost.get("parts") or {}).get(part)
    if not ms or cost is None:
        return None
    share, bound = _roofline.roofline(
        cost, run.device["kind"], ms * 1e-3, chips=run.chips)
    run.notes[part + "_roofline_bound"] = bound
    return share


@_reader
def step_attention_ms(run):
    return _scopes_ms(run, ATTENTION)


@_reader
def step_moe_ms(run):
    return _scopes_ms(run, MOE)


@_reader
def step_head_ms(run):
    return _scopes_ms(run, HEAD)


@_reader
def attention_roofline(run):
    return _part_roofline(run, "attention", ATTENTION)


@_reader
def moe_roofline(run):
    return _part_roofline(run, "moe", MOE)


@_reader
def moe_load_imbalance(run):
    fullest = _counter(run, "expert_load_max")
    mean = _counter(run, "expert_load_mean")
    return fullest / mean if fullest and mean else None


@_reader
def seq_fill_share(run):
    fill = _counter(run, "window_fill")
    return None if fill is None else 100.0 * fill
