"""The fed generator and the pairing behind episode_to_ring_p95_ms."""

import queue
import time

from benchmarks.harness import feed
from benchmarks.harness.layers import percentile
from benchmarks.harness.probes import EpisodePairing


def test_offer_order_is_seeded_and_leaves_out_the_horizon_episode():
    a, b = feed.offer_order(100, 7), feed.offer_order(100, 7)
    assert a == b and sorted(a) == list(range(1, 100))
    assert feed.offer_order(100, 8) != a
    assert feed.offer_order(100, 2**31 + 11)    # a driver-sized seed


class _Cluster:
    def __init__(self):
        self.input_queue = queue.Queue()


class _Learner:
    def __init__(self):
        self.worker = _Cluster()


class _Probes:
    def __init__(self):
        self.due = {}


def test_feeder_keeps_its_schedule_and_reports_its_own_lateness():
    learner, probes = _Learner(), _Probes()
    episodes = [{"steps": i} for i in range(5)]
    feeder = feed.Feeder(learner, probes, episodes, [1, 2, 3, 4], 200)
    feeder.start()
    time.sleep(0.25)
    feeder.stop()
    assert feeder.failure is None and not feeder.is_alive()
    n = len(feeder.offers)
    assert 40 <= n <= 52                      # 200/s for a quarter second
    dues = [due for due, _ in feeder.offers]
    gaps = [b - a for a, b in zip(dues, dues[1:])]
    assert all(abs(g - 0.005) < 1e-9 for g in gaps)   # due times: exact
    assert all(sent >= due for due, sent in feeder.offers)
    assert percentile([s - d for d, s in feeder.offers], 99) < 0.1
    got = [learner.worker.input_queue.get_nowait() for _ in range(n)]
    assert all(verb == "episode" for _, (verb, _) in got)
    steps = [payload[0]["steps"] for _, (_, payload) in got]
    assert steps[:8] == [1, 2, 3, 4, 1, 2, 3, 4]      # the order, cycled
    # every offer is an object of its own, stamped with its due time
    assert len(probes.due) == n
    assert sorted(probes.due.values()) == dues


def test_pairing_survives_shed_episodes():
    p = EpisodePairing()
    p.offered([1.0, 2.0, 3.0, 4.0, 5.0])
    p.shed_oldest(2)                 # pending overflowed: 1.0 and 2.0 go
    p.appended(2, at=10.0)           # the next append lands 3.0 and 4.0
    p.offered([6.0])
    p.appended(2, at=11.0)
    assert p.shed == [1.0, 2.0]
    assert p.landed == [(3.0, 10.0), (4.0, 10.0), (5.0, 11.0), (6.0, 11.0)]
    assert p.waiting() == 0
    p.appended(3, at=12.0)           # nothing left to pair: no error
    assert len(p.landed) == 4


def test_percentile_is_nearest_rank():
    assert percentile(list(range(1, 101)), 95) == 95
    assert percentile([5.0], 95) == 5.0
    assert percentile([1, 2, 3, 4], 50) == 2
