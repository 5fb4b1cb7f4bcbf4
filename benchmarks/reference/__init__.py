"""Plain float32 references.  Nothing here imports the program.

A configuration names the two halves of its reference and the harness
finds each as a file here (``harness/check.py::reference_setup``).

``reference: <module>``, the NET: ``forward(params, obs, hidden, lowp)``
returning the heads by name, ``RECURRENT`` and, where that is true,
``init_hidden(batch_shape)``.

``reference_training: <module>`` (absent: ``training``), the TRAINING
SIDE.  The harness reaches it only through these four functions, with
the arguments ``training.py`` gives them:

``episode_columns(episode) -> columns``
    one wire-format episode as per-step arrays.  The harness itself
    reads ``columns["length"]`` (steps held) and
    ``columns["prob"].shape[1]`` (seats); every other entry is between
    this function and ``gather``, so a policy of vocabulary width need
    keep no dense mask.
``draw(seed, step_idx, size, oldest, capacity, lengths, batch_size,
forward_steps, seats) -> (slots, starts, seat)``
    the rows of one fused step, as the program's ring draws them.
``gather(columns, slots, starts, seat, forward_steps, burn_in,
one_seat) -> batch``
    the training batch of those rows, a dict of arrays ``(B, T, P, ...)``
    (``columns`` indexed by slot).  ``run.py::_check_ring_rows`` holds
    EVERY key it returns against the same key of the program's own
    gather from the ring, exactly (``progress`` within 1e-6: it is
    divided on the device); a key the program's batch has and this one
    lacks is not compared.
``follow(net, params, batches, cfg, lowp=None) -> (losses, first,
final, scales)``
    ``len(batches)`` steps from ``params``: each step's total loss, the
    FIRST gradient as Adam was handed it (clipped, weight decay added),
    the parameters after the last step (both as host trees shaped like
    ``params``), and per step the size of the loss's parts, against
    which a gap in the total is measured.  ``lowp`` (``"bf16"``,
    ``"fp8"``) computes the net in that precision: the control goes
    through it.  ``follow`` has the chip to itself and MAY accumulate
    its gradient over blocks of the batch's rows so that it fits: the
    loss is a sum over rows.

``training.py``'s own functions keep their names, so a later module
imports what it shares (``draw``, ``target``, ``adam_step``,
``global_norm``) and writes only what differs.
"""
