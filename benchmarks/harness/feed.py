"""The one traffic generator: reads a mix's parameters and drives the
learner with them.

``fed``: an open loop.  Corpus episodes arrive one by one at
``rate_eps``, in an order drawn from the seed, on the learner's control
plane as a gather's upload does: an ``("episode", [episode])`` message
on the worker cluster's input queue, which the server thread hands to
``Learner.feed_episodes`` (the WAL and the intake counters belong to
that thread).  Each is timed from when it was DUE, and how late the
generator itself ran is reported, so that a starved generator does not
read as a fast learner.
"""

import random
import threading
import time


def offer_order(count, seed):
    """Indices 1..count-1 of the corpus (0 is the horizon-length
    episode, used once to size the ring) in a seeded order, cycled."""
    order = list(range(1, count))
    random.Random(seed).shuffle(order)
    return order


class Feeder(threading.Thread):
    def __init__(self, learner, probes, episodes, order, rate_eps):
        super().__init__(name="bench-feeder", daemon=True)
        self.learner = learner
        self.probes = probes
        self.episodes = episodes
        self.order = order
        self.interval = 1.0 / float(rate_eps)
        self.offers = []            # (due, sent)
        self.peer = object()        # stands for the gather's connection
        self._halt = threading.Event()
        self.failure = None

    def run(self):
        start = time.perf_counter()
        try:
            i = 0
            while not self._halt.is_set():
                due = start + i * self.interval
                wait = due - time.perf_counter()
                if wait > 0 and self._halt.wait(wait):
                    break
                # a copy, so that this offer has an identity of its own
                episode = dict(self.episodes[self.order[i % len(self.order)]])
                self.probes.due[id(episode)] = due
                sent = time.perf_counter()
                self.learner.worker.input_queue.put(
                    (self.peer, ("episode", [episode])))
                self.offers.append((due, sent))
                i += 1
        except Exception as exc:   # reported by the run, which fails
            self.failure = exc

    def stop(self):
        self._halt.set()
        self.join(timeout=30)
