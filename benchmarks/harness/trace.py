"""From the profiler's ``.xplane.pb`` to numbers.

``load`` turns the file into plain data (planes -> lines -> events of
``(name, start_ns, duration_ns)``); ``reduce`` works on that plain data
alone, so a test can hold it to a small recorded trace.

What a TPU trace looks like (JAX 0.9, v5e): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<hash>)``) and ``XLA Ops`` (one
event per HLO operation, named by its HLO text); host threads are lines
of the plane ``/host:CPU``, and ``jax.profiler.TraceAnnotation`` spans
land there on the same clock (the harness writes ``bench:<name>``).
"""

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path):
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        keep_all = plane.name.startswith(DEVICE_PLANE)
        if not keep_all and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if keep_all and line.name not in (MODULES_LINE, OPS_LINE):
                continue
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events
                      if keep_all or ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_label(hlo_text):
    """``%copy.288 = u8[256,8,4,7,11,17]{...} copy(...)`` ->
    ``copy.288 u8[256,8,4,7,11,17]``: the instruction's name and its
    result shape, which survive a recompile of the same program."""
    m = re.match(r"%?([\w.\-]+) = \(?([\w]+\[[\d,]*\])", hlo_text)
    label = f"{m.group(1)} {m.group(2)}" if m else hlo_text
    return label[:64]


def module_label(name):
    return name.split("(")[0]


def _union(intervals):
    """Merged, sorted intervals."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(trace, uncovered="untracked"):
    """The traced window's numbers.  Times in seconds.

    The window is the ``bench:window`` host span; device events are
    clipped to it.  ``busy_s`` is the union of the intervals in which an
    operation ran, averaged over the chips; idle gaps are the
    complement on chip 0, each named by the ``bench:`` host span that
    covers most of it (``uncovered`` when none does)."""
    devices = sorted((p for p in trace["planes"]
                      if p["name"].startswith(DEVICE_PLANE)),
                     key=lambda p: int(p["name"][len(DEVICE_PLANE):]))
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    spans = []
    for plane in trace["planes"]:
        if plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                spans += [(n, s, s + d) for n, s, d in line["events"]]
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if window:
        lo, hi = window[0][1], window[0][2]
    else:
        every = [e for p in devices for e in _line(p, OPS_LINE)]
        lo = min(s for _, s, _ in every)
        hi = max(s + d for _, s, d in every)
    spans = [s for s in spans if s[0] != WINDOW_SPAN]

    per_chip = []
    for plane in devices:
        ops = _clip(_line(plane, OPS_LINE), lo, hi)
        mods = _clip(_line(plane, MODULES_LINE), lo, hi)
        busy = _union([(a, b) for _, a, b in (ops or mods)])
        modules, op_time = {}, {}
        for name, a, b in mods:
            m = modules.setdefault(module_label(name), [0, 0.0])
            m[0] += 1
            m[1] += (b - a) * 1e-9
        for name, a, b in ops:
            label = op_label(name)
            op_time[label] = op_time.get(label, 0.0) + (b - a) * 1e-9
        per_chip.append({
            "busy_s": sum(b - a for a, b in busy) * 1e-9,
            "busy": busy, "modules": modules, "ops": op_time})

    gaps, edge = [], lo
    for a, b in per_chip[0]["busy"] + [[hi, hi]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)

    def name_gap(a, b):
        cover = {}
        for n, s, e in spans:
            overlap = min(b, e) - max(a, s)
            if overlap > 0:
                key = n[len(SPAN_PREFIX):]
                cover[key] = cover.get(key, 0.0) + overlap
        if cover and max(cover.values()) >= 0.5 * (b - a):
            return max(cover, key=cover.get)
        return uncovered

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:5]
    top_ops = sorted(per_chip[0]["ops"].items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(c["busy_s"] for c in per_chip) / len(per_chip),
        "chips": len(per_chip),
        "modules": per_chip[0]["modules"],
        "device_ops": [[n, s] for n, s in top_ops[:10]],
        "idle_gaps": [[name_gap(a, b), (b - a) * 1e-9] for a, b in longest],
    }
