"""One general traffic generator, driven by a traffic file.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    {"loop": "closed", "outstanding": 64, "k": 10, "ef": 64}

``closed``: one client thread keeps ``outstanding`` requests in flight and
sends each response's successor as soon as the response arrives.  Queries
cycle through the pool in an order drawn from the seed.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from bench.corpus import rng_for


class Record:
    """One request as the client saw it (perf_counter seconds): sent at
    ``due``, answered at ``done``."""

    __slots__ = ("pool", "due", "done", "resp", "exc")

    def __init__(self, pool: int, due: float):
        self.pool, self.due = pool, due
        self.done = self.resp = self.exc = None

    @property
    def ok(self) -> bool:
        return self.resp is not None and self.resp.status == "ok"


class Load:
    """Drives ``submit(query, k, ef) -> Future`` with one traffic mix.

    ``run(warm_s, seconds)`` sends a warm-up phase and then the measured
    window back to back; it returns the window's start on the perf_counter
    clock and the records of every request sent, the warm-up's included
    (the window's are those sent at or after the start).
    """

    def __init__(self, traffic: dict, submit, pool: np.ndarray, name: str,
                 seed: int):
        self.traffic, self.submit, self.pool = traffic, submit, pool
        self.rng = rng_for(name, seed, "traffic")
        self.order = self.rng.permutation(len(pool))
        self.k = int(traffic.get("k", 10))
        self.sent_count = 0

    def _send(self, rec: Record, on_done) -> None:
        self.sent_count += 1

        def done(fut, rec=rec):
            rec.done = time.perf_counter()
            if fut.exception() is None:
                rec.resp = fut.result()
            else:
                rec.exc = fut.exception()
            on_done()

        try:
            fut = self.submit(self.pool[rec.pool], k=self.k,
                              ef=self.traffic["ef"])
        except Exception as e:           # refused at the door: a failure
            rec.exc, rec.done = e, time.perf_counter()
            on_done()
            return
        fut.add_done_callback(done)

    def _next_pool(self) -> int:
        return int(self.order[self.sent_count % len(self.order)])

    def run(self, warm_s: float, seconds: float):
        if self.traffic["loop"] != "closed":
            raise ValueError(f"unknown loop {self.traffic['loop']!r}")
        slots = threading.Semaphore(int(self.traffic["outstanding"]))
        t0 = time.perf_counter() + warm_s
        t_end = t0 + seconds
        records = []
        while True:
            if not slots.acquire(timeout=0.05):
                if time.perf_counter() >= t_end:
                    break
                continue
            now = time.perf_counter()
            if now >= t_end:
                slots.release()
                break
            rec = Record(self._next_pool(), now)
            records.append(rec)
            self._send(rec, slots.release)
        return t0, records


def wait_all(records: list, deadline: float) -> int:
    """Wait until every record is answered or ``deadline`` (perf_counter)
    passes; returns how many never got an answer."""
    while time.perf_counter() < deadline:
        if all(r.done is not None for r in records):
            return 0
        time.sleep(0.01)
    return sum(r.done is None for r in records)
