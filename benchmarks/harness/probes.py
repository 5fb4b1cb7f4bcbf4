"""Spans and counts taken from the benchmark's side, by wrapping bound
methods of ONE learner instance (nothing of the program is edited, and
no class is patched):

  trainer._replay_step   the fused step's dispatch; the window's edges
                         are closed here, on the trainer thread, by
                         ``block_until_ready`` at a step boundary; the
                         first three calls are captured for the check
  trainer.train,
  trainer._epoch_loop_device   the epoch tail (snapshot, train-state
                         checkpoint): ``boundary``
  replay.ingest          ring ingest on the trainer thread
  replay.offer,
  replay._append_run     the FIFO pairing behind episode_to_ring

Times are ``time.perf_counter()`` seconds.  With ``annotate`` the spans
are also written into the profiler's trace (``bench:<name>``), so idle
gaps of the device can be named by what the host was doing.
"""

import contextlib
import threading
import time
from collections import deque

import jax

CHECK_STEPS = 3


class EpisodePairing:
    """Which offered episode does an append make drawable?  ``pending``
    is a FIFO that sheds its oldest, so the k-th episode appended is
    the k-th offered that was not shed."""

    def __init__(self):
        self._lock = threading.Lock()
        self._queue = deque()      # due times of episodes not yet in the ring
        self.landed = []           # (due, appended_at)
        self.shed = []             # due times of shed episodes

    def offered(self, due_times):
        with self._lock:
            self._queue.extend(due_times)

    def shed_oldest(self, count):
        with self._lock:
            for _ in range(min(count, len(self._queue))):
                self.shed.append(self._queue.popleft())

    def appended(self, count, at):
        with self._lock:
            for _ in range(min(count, len(self._queue))):
                self.landed.append((self._queue.popleft(), at))

    def waiting(self):
        with self._lock:
            return len(self._queue)


class Probes:
    def __init__(self, learner, annotate=False):
        self.learner = learner
        self.trainer = learner.trainer
        self.replay = learner.trainer.device_replay
        self.annotate = annotate
        self.spans = {"ingest": [], "boundary": []}
        self.ingested = []          # (end time, episodes appended)
        self.pairing = EpisodePairing()
        self.due = {}               # id(episode) -> due time (feeder)
        self.appends = []           # (first slot, lengths) per append
        self.captured = {"losses": []}
        self.captured_done = threading.Event()
        self._calls = 0
        self._edge_request = threading.Event()
        self._edge_done = threading.Event()
        self.edges = []             # (time, steps completed)
        self.edge_blocks = []       # seconds each edge waited for the device
        self._loop_end = None

    # -- helpers ------------------------------------------------------
    def _span(self, name):
        if self.annotate:
            return jax.profiler.TraceAnnotation("bench:" + name)
        return contextlib.nullcontext()

    def request_edge(self, timeout=120.0):
        """Close a window edge at the next step boundary: the trainer
        thread blocks until the device has finished that step.  Returns
        ``(time, steps completed)``."""
        self._edge_done.clear()
        self._edge_request.set()
        if not self._edge_done.wait(timeout):
            import faulthandler

            faulthandler.dump_traceback(all_threads=True)
            raise RuntimeError("no fused step finished within "
                               f"{timeout}s of an edge request")
        return self.edges[-1]

    # -- installation -------------------------------------------------
    def install(self):
        trainer, replay = self.trainer, self.replay
        real_step = trainer._replay_step

        def step(*args):
            with self._span("step_dispatch"):
                out = real_step(*args)
            self._calls += 1
            if self._calls <= CHECK_STEPS:
                self._capture(out)
            if self._edge_request.is_set():
                self._edge_request.clear()
                t_block = time.perf_counter()
                jax.block_until_ready(out[0])
                # trainer.steps is incremented after this call returns
                self.edges.append((time.perf_counter(), trainer.steps + 1))
                # how far the host had run ahead of the device
                self.edge_blocks.append(self.edges[-1][0] - t_block)
                self._edge_done.set()
            return out

        trainer._replay_step = step

        real_loop, real_train = trainer._epoch_loop_device, trainer.train

        def loop():
            try:
                return real_loop()
            finally:
                self._loop_end = time.perf_counter()
                self._boundary = self._span("boundary")
                self._boundary.__enter__()

        def train():
            try:
                return real_train()
            finally:
                if self._loop_end is not None:
                    self._boundary.__exit__(None, None, None)
                    self.spans["boundary"].append(
                        (self._loop_end, time.perf_counter()))
                    self._loop_end = None

        trainer._epoch_loop_device = loop
        trainer.train = train

        real_ingest = replay.ingest

        def ingest(*args, **kwargs):
            if not self.captured_done.is_set():
                # the check's three steps run on the ring as primed
                return None
            before = replay.episodes_seen
            t0 = time.perf_counter()
            with self._span("ingest"):
                out = real_ingest(*args, **kwargs)
            done = replay.episodes_seen - before
            if done:
                t1 = time.perf_counter()
                self.spans["ingest"].append((t0, t1))
                self.ingested.append((t1, done))
            return out

        replay.ingest = ingest

        real_offer, real_append = replay.offer, replay._append_run

        def offer(episodes):
            episodes = [e for e in episodes if e is not None]
            now = time.perf_counter()
            dropped = replay.dropped
            self.pairing.offered(
                [self.due.pop(id(e), now) for e in episodes])
            real_offer(episodes)
            self.pairing.shed_oldest(replay.dropped - dropped)

        def append_run(cols):
            first = replay.write_ptr
            real_append(cols)
            self.appends.append(
                (first, [len(c["turn_idx"]) for c in cols]))
            self.pairing.appended(len(cols), time.perf_counter())

        replay.offer = offer
        replay._append_run = append_run

    def _capture(self, out):
        """One of the first three steps: keep its loss, after the first
        Adam's first moment, after the third the params."""
        from .check import adam_first_moment

        params, opt_state, metrics = out[0], out[1], out[2]
        self.captured["losses"].append(float(metrics["total"]))
        if self._calls == 1:
            self.captured["mu_after_first"] = jax.device_get(
                adam_first_moment(opt_state))
        if self._calls == CHECK_STEPS:
            self.captured["params_after_third"] = jax.device_get(params)
            self.captured_done.set()
