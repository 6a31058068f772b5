import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bench import corpus, loadgen

CLOSED = {"loop": "closed", "outstanding": 4, "k": 10, "ef": 32}


class Backend:
    """``submit`` that answers each request after ``delay_s`` on a thread
    of its own, and records how many were in flight at once."""

    def __init__(self, delay_s=0.002, refuse=False):
        self.delay_s, self.refuse = delay_s, refuse
        self.lock = threading.Lock()
        self.in_flight = self.most = 0
        self.calls = []
        self.threads = []

    def _answer(self, fut):
        time.sleep(self.delay_s)
        with self.lock:
            self.in_flight -= 1
        fut.set_result(type("Resp", (), {"status": "ok"})())

    def submit(self, query, k, ef):
        if self.refuse:
            raise RuntimeError("queue full")
        self.calls.append((tuple(query), k, ef))
        with self.lock:
            self.in_flight += 1
            self.most = max(self.most, self.in_flight)
        fut = Future()
        t = threading.Thread(target=self._answer, args=(fut,))
        self.threads.append(t)
        t.start()
        return fut


def _pool(n=16):
    return np.arange(n * 2, dtype=np.float32).reshape(n, 2)


def test_closed_loop_keeps_outstanding_requests_in_flight():
    be = Backend()
    load = loadgen.Load(CLOSED, be.submit, _pool(), "x", 2**31 + 7)
    t0, records = load.run(0.05, 0.2)
    for t in be.threads:
        t.join()
    assert be.most == CLOSED["outstanding"]
    assert all(r.ok for r in records)
    window = [r for r in records if r.due >= t0]
    assert 0 < len(window) < len(records)
    assert all(r.done >= r.due for r in records)
    assert {(k, ef) for _, k, ef in be.calls} == {(10, 32)}


def test_closed_loop_cycles_the_pool_in_a_seeded_order():
    pool = _pool()

    def order(seed):
        load = loadgen.Load(CLOSED, Backend(0.0).submit, pool, "x", seed)
        out = []
        for i in range(32):
            load.sent_count = i
            out.append(load._next_pool())
        return out

    a, b, c = order(5), order(5), order(6)
    assert a == b and a != c
    assert sorted(a[:16]) == list(range(16)) and a[16:] == a[:16]


def test_a_refused_request_is_a_failure_and_frees_its_slot():
    load = loadgen.Load(CLOSED, Backend(refuse=True).submit, _pool(), "x", 1)
    _, records = load.run(0.0, 0.05)
    assert len(records) > CLOSED["outstanding"]
    assert all(not r.ok and r.exc is not None for r in records)


def test_wait_all_counts_what_never_came():
    done, never = loadgen.Record(0, 0.0), loadgen.Record(1, 0.0)
    done.done = 1.0
    assert loadgen.wait_all([done], time.perf_counter() + 1) == 0
    assert loadgen.wait_all([done, never], time.perf_counter() + 0.05) == 1


def test_an_unknown_loop_is_refused():
    load = loadgen.Load({"loop": "open", "k": 10, "ef": 32},
                        Backend().submit, _pool(), "x", 1)
    with pytest.raises(ValueError):
        load.run(0.0, 0.01)


def test_corpus_is_a_function_of_the_seed():
    cfg = {"name": "c", "n_base": 100, "dim": 8, "metric": "l2",
           "n_queries": 10, "n_train_queries": 5,
           "assumed": {"spectrum_decay": 1.0, "n_clusters": 4,
                       "cluster_spread": 0.5, "query_noise": 0.25}}
    a, b = corpus.generate(cfg, 7), corpus.generate(cfg, 7)
    c = corpus.generate(cfg, 8)
    np.testing.assert_array_equal(a.vectors, b.vectors)
    np.testing.assert_array_equal(a.queries, b.queries)
    assert not np.array_equal(a.vectors, c.vectors)
    assert a.vectors.dtype == np.float32 and a.queries.shape == (10, 8)
