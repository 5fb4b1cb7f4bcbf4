"""Readers of what a latent-attention net with a next-next-token module
says of those two parts: ``Trainer.step_profile()``'s ``scopes`` under
``net.attention.latent`` and ``net.mtp``, and the counter
``mtp_target_share``.  As ``sequence_parts.py``'s readers, whose helpers
these use: none raises; where the program has no such scope or counter
(another net, or a program older than they are) the reader returns None
and the line leaves the metric out."""

from .program_spans import _reader
from .sequence_parts import _counter, _part_roofline, _scopes_ms

LATENT = ("net.attention.latent",)
MTP = ("net.mtp",)


@_reader
def step_latent_attention_ms(run):
    return _scopes_ms(run, LATENT)


@_reader
def latent_attention_roofline(run):
    return _part_roofline(run, "attention", LATENT)


@_reader
def step_mtp_ms(run):
    return _scopes_ms(run, MTP)


@_reader
def mtp_target_share(run):
    share = _counter(run, "mtp_target_share")
    return None if share is None else 100.0 * share
