"""The comparison that decides ``correct``.

Training cells: the first three fused steps of the very object the
window then drives (same compiled program, same ring, same state
threading) are held against the plain reference following the same
three steps from the same weights and the same episodes:

  loss_gap         worst |program - reference| over the three steps'
                   total losses, against the size of the reference
                   loss's parts (the total can pass through zero)
  grad_gap         worst leaf of | ||g_prog|| - ||g_ref|| | over
                   max(||g_ref|| of the leaf, median leaf ||g_ref||),
                   g = the first gradient as Adam was handed it
                   (recovered from Adam's first moment after one step)
  grad_diff        worst leaf of ||g_prog - g_ref|| over the same
                   denominator: the number that separates the stated
                   bfloat16 from the fp8 control most widely (Geese 12x,
                   Geister 6x; the norm gap alone separates Geister's
                   recurrent body only 1.3x); the denominator keeps
                   near-zero leaves, whose sign is noise, from ruling it
  update_gap       the by-leaf norm gap on the parameters' change after
                   the three steps
  ring_mismatch    rows of a seeded sample of windows, fetched back from
                   the ring's in-window appends, that differ from the
                   episodes they were made from (exact: limit 0)
  unaccounted      episodes offered or received that are neither in the
                   ring, nor shed, nor rejected, nor still queued (0)

Each configuration's file carries its own limits (``check_limits``),
set from readings of sound runs and of the control on the chip; the
readings are in PERF.md.
"""

from collections.abc import Iterator

import jax
import numpy as np

ADAM_B1 = 0.9


def adam_first_moment(opt_state):
    """The optimiser state's Adam ``mu`` tree, wherever the chain
    holds it."""
    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu") and hasattr(x, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} Adam states in the optimiser")
    return found[0].mu


def leaf_norms(tree, minus=None):
    """The float64 norm of every leaf of ``tree`` (of ``tree - minus``
    where a second tree is given), walking the leaves in step with ONE
    leaf in float64 at a time.  No float64 copy of a whole tree is ever
    made: at hundreds of millions of parameters those were gigabytes of
    host memory apiece."""
    others = None if minus is None else jax.tree.leaves(minus)
    norms = []
    for leaf in _leaves(tree):
        other = None if others is None else others[len(norms)]
        norms.append(_norm(leaf, other))
        del leaf        # one an iterator made goes before the next is made
    if others is not None and len(norms) != len(others):
        raise ValueError(f"{len(norms)} leaves against {len(others)}")
    return np.asarray(norms)


def _norm(leaf, minus):
    """numpy casts the operands block by block into the one float64
    result, which is then squared in place."""
    if minus is None:
        wide = np.square(leaf, dtype=np.float64)
    else:
        wide = np.subtract(leaf, minus, dtype=np.float64)
        np.square(wide, out=wide)
    return float(np.sqrt(np.sum(wide)))


def _leaves(tree):
    """A tree's leaves; an iterator that makes its leaves one at a time
    (``training_numbers``' first gradient) passes as it is."""
    return tree if isinstance(tree, Iterator) else jax.tree.leaves(tree)


def worst_leaf_gap(program, reference, minus=None):
    """Gap between the two trees' per-leaf norms, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero).  With ``minus`` the norms
    are those of each tree's change from it."""
    p, r = leaf_norms(program, minus), leaf_norms(reference, minus)
    return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))


def worst_leaf_difference(program, reference):
    """Per-leaf norm of the DIFFERENCE, against the same denominator."""
    r = leaf_norms(reference)
    return float(np.max(leaf_norms(program, minus=reference)
                        / np.maximum(r, np.median(r))))


def training_numbers(captured, reference, initial):
    """``captured``: the program's first three steps (losses, Adam mu
    after step one, params after step three); ``reference``: the plain
    follow's (losses, first gradient, final params)."""
    ref_losses, ref_first, ref_final, ref_scales = reference

    def prog_first():
        # the gradient Adam was handed, a float32 leaf at a time
        return (np.asarray(m) / (1 - ADAM_B1)
                for m in jax.tree.leaves(captured["mu_after_first"]))

    return {
        "loss_gap": max(abs(p - r) / scale for p, r, scale in
                        zip(captured["losses"], ref_losses, ref_scales)),
        "grad_gap": worst_leaf_gap(prog_first(), ref_first),
        "grad_diff": worst_leaf_difference(prog_first(), ref_first),
        "update_gap": worst_leaf_gap(
            captured["params_after_third"], ref_final, minus=initial),
    }


def verdict(numbers, limits):
    """(correct, lines): every number beside its limit."""
    lines, ok = [], True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        lines.append(f"check {name} {value:.6g} limit {limit:g} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines


def reference_setup(config, train):
    """The configuration's plain reference, both halves found by the
    names in its file as the per-layer readers are (the interface is
    written once, in ``benchmarks/reference/__init__.py``): the NET,
    ``benchmarks/reference/<reference>.py``, and the TRAINING SIDE,
    ``benchmarks/reference/<reference_training>.py`` (absent:
    ``training``).  A configuration with a new net, or one whose window
    the default training side cannot follow (a sequence along the time
    axis, columns without a dense mask, a batch to follow in blocks),
    brings each as a file of its own."""
    import importlib

    name = config.get("reference_training", "training")
    training = importlib.import_module("benchmarks.reference." + name)
    net = importlib.import_module(
        "benchmarks.reference." + config["reference"])
    if (name == "training" and train["turn_based_training"]
            and not train["observation"]):
        # the default module's own lack; one brought by name that has
        # the gather is not refused
        raise NotImplementedError(
            "the plain reference has no turn-player gather yet")
    return training, net, not train["turn_based_training"]


def reference_batches(config, train, primed, capacity, steps=3):
    """``(training, net, batches)``: the plain reference's own draw and
    its own gather from the episodes, for the first ``steps`` fused
    steps of a ring primed with ``primed`` (slot i holds
    ``primed[i]``)."""
    training, net, one_seat = reference_setup(config, train)
    columns = [training.episode_columns(ep) for ep in primed]
    lengths = np.zeros(capacity + 1, np.int32)
    lengths[:len(columns)] = [c["length"] for c in columns]
    seats = columns[0]["prob"].shape[1] if one_seat else 0
    batches = []
    for step_idx in range(steps):
        slots, starts, seat = training.draw(
            train["seed"], step_idx, len(columns), 0, capacity, lengths,
            train["batch_size"], train["forward_steps"], seats)
        batches.append(training.gather(
            columns, slots, starts, seat, train["forward_steps"],
            train["burn_in_steps"], one_seat))
    return training, net, batches


def reference_follow(config, train, primed, capacity, initial_params,
                     steps=3, lowp=None):
    """The plain reference through those steps: its own loss, gradient
    and Adam over its own batches.  ``lowp`` computes it in a lower
    precision: the control."""
    training, net, batches = reference_batches(
        config, train, primed, capacity, steps)
    return training.follow(net, initial_params, batches, train, lowp)


def as_captured(followed):
    """A reference follow, put in the program's place: what the probes
    would have captured from it."""
    losses, first, final, _scales = followed
    return {"losses": losses,
            "mu_after_first": jax.tree.map(
                lambda g: np.asarray(g) * (1 - ADAM_B1), first),
            "params_after_third": final}
