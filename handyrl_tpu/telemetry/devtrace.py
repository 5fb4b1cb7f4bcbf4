"""From the program's own device traces to numbers: the fused step's
phases, and the device's idle gaps by what the host was doing.

``load`` turns a profiler ``.xplane.pb`` into plain data; everything
else works on that plain data alone, so a test holds it to a small
hand-written trace (the discipline of ``benchmarks/harness/trace.py``,
whose reduction the benchmark keeps for itself).

What a TPU trace carries (JAX 0.9, v5e, taken with
``utils.profiling.profiler_options``): per chip a plane
``/device:TPU:<n>`` with the line ``XLA Modules`` (one event per
executed program, ``jit_<function>(<hash>)``) and ``XLA Ops`` (one event
per executed HLO instruction, named by its HLO text ``%copy.288 = ...``;
the body of a ``while`` nests inside the ``while``'s own event).  With
the HLO proto off an op event carries NO scope of its own (its stats are
its times), so the scope of an op comes from ONE source: the compiled
step's HLO text, whose ``metadata={op_name="jit(step)/net.forward/..."}``
is joined on the instruction names the events bear.  The program's live
spans lie on the plane ``/host:CPU`` as ``hrl:<name>``
(``telemetry.spans``), one line per host thread, on the same clock.

Nothing here imports jax at module level (the :mod:`.spans`
discipline); ``load`` imports ``jax.profiler.ProfileData`` when called.
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "hrl:"        # spans.MIRROR_PREFIX

# scope (jax.named_scope in staging.py / ops/update.py / ops/losses.py)
# -> phase.  Every op under ``transpose(`` is the backward pass,
# whatever scope it transposes.
PHASE_OF_SCOPE = {
    "replay.draw": "gather",
    "replay.gather": "gather",
    "net.forward": "forward",
    "loss.targets": "targets",
    "loss.terms": "targets",
    "optimizer": "optimizer",
}
RING_ARGUMENT = "buffers["  # step(params, opt_state, buffers, state)
PHASES = ("gather", "forward", "targets", "backward", "optimizer",
          "unscoped")
_SCOPE = re.compile(
    r"(?:^|[/(])(" + "|".join(re.escape(s) for s in PHASE_OF_SCOPE)
    + r")(?=[/)]|$)")
# a net's own scopes inside ``net.forward`` (``net.attention.window``,
# ``net.moe.experts``, ``net.head``, ...: models/sequence_net.py), by
# which a step's time is also told apart, forward and transpose together
_NET_SCOPE = re.compile(r"(?:^|[/(])(net\.[\w.]+)(?=[/)]|$)")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+)(?: = |$)")
_DEFINITION = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
KERNEL_SUFFIX = "pallas_call"    # the last part of a kernel's op_name
_NS_SLACK = 2.0             # event times are rounded to whole ns


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def instruction(text):
    """``%copy.288 = u8[256,8]{...} copy(...)`` -> ``copy.288``."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text


def op_names(hlo_text):
    """Compiled HLO text -> ``{instruction name: op_name}`` for every
    instruction of the text ("" where the compiler gave it none).  An
    instruction's text runs from the line that defines it to the next
    definition: a kernel's custom call is written over three lines (its
    ``kernel_metadata`` holds newlines) and bears its ``op_name`` on
    the last."""
    table, name = {}, None
    for line in (hlo_text or "").splitlines():
        m = _DEFINITION.match(line)
        if m is not None:
            name = m.group(1)
            table[name] = ""
        if name is not None and not table[name]:
            scope = _OP_NAME.search(line)
            if scope is not None:
                table[name] = scope.group(1)
    return table


def module_name(hlo_text, default="jit_step"):
    """``HloModule jit_step, ...`` -> ``jit_step``."""
    m = re.match(r"\s*HloModule\s+([\w.\-]+)", hlo_text or "")
    return m.group(1) if m else default


def load(path, hlo_text=""):
    """``.xplane.pb`` -> ``{"planes": [...], "op_names": {...},
    "module": "jit_step"}``: device planes keep their modules and ops
    as ``(name, start_ns, duration_ns)`` with ops cut down to their
    instruction names, the host plane keeps the program's ``hrl:``
    spans; the text gives the instructions' scopes and the step
    program's name."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            ops = device and line.name == OPS_LINE
            events = [(instruction(ev.name) if ops else ev.name,
                       float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events
                      if device or ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "op_names": op_names(hlo_text),
            "module": module_name(hlo_text)}


def phase_of(op_name):
    """The phase an ``op_name`` belongs to, or None when no scope of
    the step claims it.  The compiler's own copy of one of the ring's
    arrays (it re-lays the per-slot ``outcome`` before the gather
    reads it) is no traced op and bears the ARGUMENT's name,
    ``buffers['outcome']``: only the draw and the gather read the
    ring, so it is gather."""
    if "transpose(" in op_name:
        return "backward"
    if op_name.startswith(RING_ARGUMENT):
        return "gather"
    scopes = _SCOPE.findall(op_name)
    return PHASE_OF_SCOPE[scopes[-1]] if scopes else None


def net_scope_of(op_name):
    """The innermost scope ``net.<part>`` an ``op_name`` lies in,
    ``net.forward`` itself aside; None where it lies in none."""
    scopes = [s for s in _NET_SCOPE.findall(op_name) if s != "net.forward"]
    return scopes[-1] if scopes else None


def _chip0(trace):
    devices = sorted((p for p in trace["planes"]
                      if p["name"].startswith(DEVICE_PLANE)),
                     key=lambda p: int(p["name"][len(DEVICE_PLANE):]))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    return devices[0]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _top_level(ops):
    """Op events that lie inside no other: a ``while``'s body runs
    inside the ``while``'s own event."""
    out, end = [], float("-inf")
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        if start < end and start + dur <= end + _NS_SLACK:
            continue
        out.append((name, start, dur))
        end = max(end, start + dur)
    return out


def step_phases(trace, module=None):
    """Per executed step of program ``module`` (the trace's own step
    program, ``jit_step``, unless named) on chip 0: ``{steps,
    step_ms, phases: {gather, forward, targets, backward, optimizer,
    unscoped: ms}}``.  Each top-level op inside a step's module event
    counts whole for the phase its ``op_name`` names; ``unscoped`` is
    what is left of the step's device time — ops no scope claims and
    the device's own gaps between ops — so the six sum to ``step_ms``.
    Two parts of ``unscoped`` are given beside it: ``op_gap_ms``, the
    step's time in which no op ran at all, and ``unmatched_ms``, ops
    whose instruction the HLO text does not hold (a text of another
    compile).  ``scopes`` tells the same time apart by the net's own
    scopes (``net_scope_of``; ms a step, an op under ``transpose(``
    counted with its forward; empty for a net that names none), and
    ``kernel_ms`` what of it ran in hand-written kernels (ops whose
    ``op_name`` ends in ``pallas_call``; by the same scopes, ``other``
    outside them; empty for a step that runs none).  A
    text that names no scope of the step at all (none
    was kept, or the executable came from a compile-cache entry that a
    build without the scopes wrote) is an error, not a step that is
    all ``unscoped``."""
    plane = _chip0(trace)
    module = module or trace.get("module") or "jit_step"
    steps = sorted((start, start + dur)
                   for name, start, dur in _line(plane, MODULES_LINE)
                   if name.split("(")[0] == module)
    if not steps:
        raise ValueError(f"the trace holds no {module} event")
    names = trace.get("op_names") or {}
    if not any(_SCOPE.search(op_name) for op_name in names.values()):
        raise ValueError(
            f"the text of {module} names no scope of the step (none "
            "kept, or a compile-cache entry of a build without them)")
    total = dict.fromkeys(PHASES, 0.0)
    scopes, kernels = {}, {}
    unmatched, covered, at = 0.0, 0.0, 0
    for name, start, dur in _top_level(_line(plane, OPS_LINE)):
        while at < len(steps) and steps[at][1] <= start:
            at += 1
        if at == len(steps):
            break
        if start < steps[at][0]:
            continue            # an op of another program
        covered += dur
        if name not in names:
            unmatched += dur
        op_name = names.get(name, "")
        phase = phase_of(op_name)
        if phase is not None:
            total[phase] += dur
        part = net_scope_of(op_name)
        if part is not None:
            scopes[part] = scopes.get(part, 0.0) + dur
        if op_name.endswith(KERNEL_SUFFIX):
            part = part or "other"
            kernels[part] = kernels.get(part, 0.0) + dur
    step_ns = sum(b - a for a, b in steps)
    total["unscoped"] = step_ns - sum(total.values())
    per_step = 1e-6 / len(steps)
    return {"steps": len(steps), "step_ms": step_ns * per_step,
            "phases": {k: v * per_step for k, v in total.items()},
            "scopes": {k: v * per_step for k, v in sorted(scopes.items())},
            "kernel_ms": {k: v * per_step
                          for k, v in sorted(kernels.items())},
            "op_gap_ms": (step_ns - covered) * per_step,
            "unmatched_ms": unmatched * per_step}


def _innermost(spans, lo, hi):
    """``{name: ns}`` of ``[lo, hi)``: each instant goes to the
    innermost (shortest) span covering it on the first thread that has
    one, in the order of ``spans``' thread ranks; ``untracked`` where
    no thread has any.  A span is ``(name, start, end, rank)``."""
    inside = [(rank, e - s, n, max(s, lo), min(e, hi))
              for n, s, e, rank in spans if e > lo and s < hi]
    cuts = sorted({lo, hi} | {x for _, _, _, s, e in inside
                              for x in (s, e)})
    cover = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [c[:3] for c in inside if c[3] <= a and c[4] >= b]
        name = min(over)[2] if over else "untracked"
        cover[name] = cover.get(name, 0.0) + (b - a)
    return cover


def _host_spans(trace):
    """The program's spans as ``(name, start, end, rank)``: rank 0 for
    the thread that dispatches the steps (the line that holds
    ``hrl:trainer.update``), 1 for every other thread — spans nest
    within a thread, not across threads, and it is the dispatching
    thread whose business leaves the device idle."""
    lines = [[(name[len(SPAN_PREFIX):], start, start + dur)
              for name, start, dur in line["events"]
              if name.startswith(SPAN_PREFIX)]
             for p in trace["planes"] if p["name"] == HOST_PLANE
             for line in p["lines"]]
    steps = [sum(1 for n, _, _ in line if n == "trainer.update")
             for line in lines]
    trainer = steps.index(max(steps)) if steps and max(steps) else -1
    return [(n, s, e, 0 if at == trainer else 1)
            for at, line in enumerate(lines) for n, s, e in line]


def idle_gaps(trace, top=5):
    """Chip 0 between its first and last op: ``{window_s, busy_share
    (%), gaps: [[name, seconds, {span: seconds}], ...]}`` — the ``top``
    longest gaps, each split among the innermost ``hrl:`` spans of its
    every instant (the dispatching thread's first, another thread's
    where that one is in none) and named by the span with the largest
    part; ``untracked`` is what no span covers."""
    plane = _chip0(trace)
    events = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
    if not events:
        raise ValueError("the device plane holds no event")
    busy = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if busy and start <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], start + dur)
        else:
            busy.append([start, start + dur])
    lo, hi = busy[0][0], busy[-1][1]
    spans = _host_spans(trace)
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)[:top]
    named = []
    for length, a, b in gaps:
        split = _innermost(spans, a, b)
        named.append([max(split, key=split.get), length * 1e-9,
                      {n: ns * 1e-9 for n, ns in split.items()}])
    return {"window_s": (hi - lo) * 1e-9,
            "busy_share": 100.0 * sum(b - a for a, b in busy) / (hi - lo),
            "gaps": named}


def format_phases(step):
    kernels = step.get("kernel_ms") or {}
    return ("steps:%d step:%.3fms " % (step["steps"], step["step_ms"])
            + " ".join("%s:%.3f" % (k, step["phases"][k]) for k in PHASES)
            + "".join(" kernel[%s]:%.3f" % kv for kv in kernels.items()))


def format_gaps(idle):
    return ("busy %.1f%% of %.3fs; longest gaps " % (
        idle["busy_share"], idle["window_s"])
        + " ".join("%s:%.4fs" % (name, seconds)
                   for name, seconds, _ in idle["gaps"]))
