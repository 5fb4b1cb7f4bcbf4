"""The arithmetic behind the per-layer readers in
``benchmarks/layer_metrics/``: each takes the finished run (``Run`` in
run.py) and returns a number, or None when it finds nothing to read."""

from . import roofline as _roofline

STEP_MODULE = "jit_step"      # jax.jit(step) in the program's staging.py


def _in_window(run, spans):
    lo, hi = run.t_open, run.t_close
    return [(max(a, lo), min(b, hi)) for a, b in spans if b > lo and a < hi]


def _share(run, name):
    spans = _in_window(run, run.probes.spans[name])
    return 100.0 * sum(b - a for a, b in spans) / run.window_s


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


# -- generator --------------------------------------------------------
def offer_late_p99_ms(run):
    late = [sent - due for due, sent in run.feeder.offers
            if run.t_open <= due < run.t_close]
    return 1e3 * percentile(late, 99) if late else None


# -- conductor / ring ingest (trainer thread) -------------------------
def boundary_thread_share(run):
    return _share(run, "boundary")


def ingest_thread_share(run):
    return _share(run, "ingest")


def ingest_ms_per_episode(run):
    episodes = sum(n for t, n in run.probes.ingested
                   if run.t_open <= t < run.t_close)
    spans = _in_window(run, run.probes.spans["ingest"])
    if not episodes:
        return None
    return 1e3 * sum(b - a for a, b in spans) / episodes


def ingest_wait_p95_ms(run):
    waits = run.episode_waits()
    return 1e3 * percentile(waits, 95) if waits else None


# -- fused step / kernels / device (traced runs) ----------------------
def step_device_ms(run):
    if run.trace is None:
        return None
    count, seconds = run.trace["modules"].get(STEP_MODULE, (0, 0.0))
    return 1e3 * seconds / count if count else None


def fused_step_roofline(run):
    ms = step_device_ms(run)
    if ms is None:
        return None
    share, bound = _roofline.roofline(
        run.step_cost, run.device["kind"], ms * 1e-3, chips=run.chips)
    run.notes["fused_step_roofline_bound"] = bound
    return share


def device_idle_share(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def hbm_peak_gb(run):
    return run.device["memory_peak_bytes"] / 1e9


def window_compiles(run):
    return float(run.window_compiles)
