"""The load generator: due times, lateness, and the same load for every
seed."""
import threading
import time

import os

import numpy as np
import pytest

import corpus
import harness

Arrivals = harness.load_module(
    os.path.join(harness.BENCH, "drivers", "ingest.py"), "driver_ingest").Arrivals

CORPUS = {"shard_tokens": 1024, "vocab_size": 256, "doc_len_median": 20,
          "doc_len_sigma": 1.2, "doc_len_cap": 500}


class Queues:
    """Stands in for the engine's queues: records each put, optionally
    taking ``hold`` seconds per put (a slow consumer)."""

    def __init__(self, hold=0.0):
        self.hold, self.items, self.closed = hold, [], threading.Event()

    def put(self, item):
        time.sleep(self.hold)
        self.items.append(item)
        return True

    def close(self):
        self.closed.set()


def _run(traffic, seconds, hold=0.0, seed=5):
    q = Queues(hold)
    a = Arrivals(q, seed, CORPUS, traffic)
    a.stop_at = time.time() + seconds
    a.start()
    a.join(timeout=seconds + 30)
    assert not a.is_alive() and a.error is None and q.closed.is_set()
    return a, q


POISSON = {"arrivals": "poisson", "rate_shards_per_s": 200.0}


def test_poisson_due_times_and_lateness():
    a, q = _run(POISSON, 1.0)
    due, put = np.asarray(a.due), np.asarray(a.put_at)
    assert len(due) == len(q.items) > 50
    assert np.all(np.diff(due) > 0)
    assert due[-1] < a.stop_at
    lag = put - due
    assert np.all(lag > -1e-3)                 # never put before due
    assert np.percentile(lag, 50) < 0.05       # an idle consumer keeps up


def test_a_slow_consumer_makes_later_shards_late():
    a, _ = _run(POISSON, 1.0, hold=0.02)       # takes 50/s of 200/s offered
    lag = np.asarray(a.put_at) - np.asarray(a.due)
    # the stall counts against every later shard: lateness grows
    assert lag[-10:].mean() > lag[:10].mean() + 0.2


def test_every_seed_offers_the_same_gaps_in_its_own_order():
    a = Arrivals(None, 1, CORPUS, POISSON)
    b = Arrivals(None, 2, CORPUS, POISSON)
    ga, gb = a.gaps(0), b.gaps(0)
    assert not np.array_equal(ga, gb)
    np.testing.assert_allclose(np.sort(ga), np.sort(gb))
    assert np.mean(ga) == pytest.approx(1 / 200.0, rel=0.02)


def test_backlog_stops_on_a_whole_epoch():
    a, q = _run({"arrivals": "backlog", "epoch_items": 4}, 0.3)
    assert len(q.items) % 4 == 0 and len(q.items) > 0


def test_shards_are_made_again_from_seed_and_index():
    x = corpus.shard_docs(2**33 + 7, 3, CORPUS)
    y = corpus.shard_docs(2**33 + 7, 3, CORPUS)
    assert sum(map(len, x)) == CORPUS["shard_tokens"]
    assert all(np.array_equal(p, r) for p, r in zip(x, y))
    assert not np.array_equal(np.concatenate(x),
                              np.concatenate(corpus.shard_docs(2**33 + 7, 4, CORPUS)))
