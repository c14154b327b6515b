"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix names its corpus (``corpus``), the rendition it is served from
(``serve_rendition``) and its arrivals (``arrivals``):

* ``{"kind": "closed", "backlog": B}`` -- a bulk scan: the client keeps B
  requests outstanding, refilling as answers drain, and walks the corpus in
  a fresh seeded permutation each pass;
* ``{"kind": "poisson", "rate_per_s": R}`` -- independent users: R x seconds
  requests due at uniform order statistics of the window (a Poisson process
  conditioned on its count, so every seed offers the same amount of work),
  each for a seeded uniform corpus item.

The client times every request from when it was due to its release by
``drain()``, and annotates its own calls into the runtime for the trace.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from smolbench.corpus import rng_for

DRAIN_POLL_S = 0.05
LATE_WAIT_S = 60.0  # how long past the window's close an answer may take


def permutation_cycle(n_items: int, seed: int):
    """Corpus indices, a new seeded permutation per pass, without end."""
    rng = rng_for(seed, 1)
    while True:
        yield from rng.permutation(n_items).tolist()


def poisson_schedule(rate: float, start: float, seconds: float, n_items: int, seed: int, stream: int):
    """(due offset, corpus index) pairs: round(rate x seconds) arrivals
    spread as sorted uniform points over [start, start + seconds)."""
    rng = rng_for(seed, stream)
    n = int(round(rate * seconds))
    due = np.sort(rng.uniform(start, start + seconds, n))
    return list(zip(due.tolist(), rng.integers(0, n_items, n).tolist()))


class Client:
    """Drives one runtime with one mix, and keeps every request's record."""

    def __init__(self, rt, items, query_cls, annotate):
        self.rt = rt
        self.items = items
        self.query_cls = query_cls
        self.annotate = annotate
        self.lock = threading.Lock()
        # uid -> [corpus index, due, submitted, released, error, scores]
        self.records: dict[int, list] = {}

    def submit(self, idx: int, due: float) -> None:
        # the record is made under the lock the drainer takes, so an answer
        # that drains at once still finds it
        with self.annotate("client.submit"), self.lock:
            t = time.perf_counter()
            uid = self.rt.submit(self.query_cls(self.items[idx]))
            self.records[uid] = [idx, due, t, None, None, None]

    def drain(self, timeout: float) -> int:
        with self.annotate("client.drain"):
            done = self.rt.drain(timeout=timeout)
        t = time.perf_counter()
        with self.lock:
            for r in done:
                rec = self.records[r.uid]
                rec[3] = t
                rec[4] = r.error
                if r.error is None:  # a typed result carries scores, a raw one its output
                    scores = getattr(r, "scores", None)
                    rec[5] = np.asarray(r.output if scores is None else scores)
        return len(done)

    def outstanding(self) -> int:
        with self.lock:
            return sum(1 for rec in self.records.values() if rec[3] is None)

    def wait_all(self, deadline: float) -> None:
        while self.outstanding() and time.perf_counter() < deadline:
            self.drain(DRAIN_POLL_S)


def run_closed(client: Client, arrivals: dict, n_items: int, seed: int, warm_items: int,
               seconds: float, on_window) -> tuple[float, float]:
    """Closed loop with a backlog.  Warm until ``warm_items`` answers have
    drained, then measure ``seconds``.  Returns the window (t0, t1)."""
    order = permutation_cycle(n_items, seed)
    backlog = arrivals["backlog"]

    def refill():
        for _ in range(backlog - client.outstanding()):
            client.submit(next(order), time.perf_counter())

    released = 0
    refill()
    while released < warm_items:
        released += client.drain(DRAIN_POLL_S)
        refill()
    t0 = time.perf_counter()
    on_window(True)
    t1 = t0 + seconds
    while (now := time.perf_counter()) < t1:
        client.drain(min(DRAIN_POLL_S, t1 - now))
        refill()
    on_window(False)
    client.wait_all(time.perf_counter() + LATE_WAIT_S)
    return t0, t1


def run_poisson(client: Client, arrivals: dict, n_items: int, seed: int, warm_s: float,
                seconds: float, on_window) -> tuple[float, float]:
    """Open loop: a submitter thread sends each request when due, while this
    thread drains.  ``warm_s`` seconds of the same traffic run first."""
    rate = arrivals["rate_per_s"]
    plan = poisson_schedule(rate, -warm_s, warm_s, n_items, seed, 2)
    plan += poisson_schedule(rate, 0.0, seconds, n_items, seed, 3)
    t0 = time.perf_counter() + warm_s + 0.05
    failure: list[BaseException] = []

    def submitter():
        try:
            for due, idx in plan:
                at = t0 + due
                with client.annotate("client.wait_due"):
                    while (lag := at - time.perf_counter()) > 0:
                        time.sleep(lag)
                client.submit(idx, at)
        except BaseException as e:  # noqa: BLE001 -- reported by the caller
            failure.append(e)

    thread = threading.Thread(target=submitter, name="smolbench-submit")
    thread.start()
    try:
        while time.perf_counter() < t0:
            client.drain(DRAIN_POLL_S)
        on_window(True)
        t1 = t0 + seconds
        while (now := time.perf_counter()) < t1:
            client.drain(min(DRAIN_POLL_S, t1 - now))
        on_window(False)
    finally:
        thread.join()
    if failure:
        raise failure[0]
    client.wait_all(time.perf_counter() + LATE_WAIT_S)
    return t0, t1


def run(client: Client, arrivals: dict, n_items: int, seed: int, warm: dict, seconds: float,
        on_window) -> tuple[float, float]:
    if arrivals["kind"] == "closed":
        return run_closed(client, arrivals, n_items, seed, warm["items"], seconds, on_window)
    if arrivals["kind"] == "poisson":
        return run_poisson(client, arrivals, n_items, seed, warm["seconds"], seconds, on_window)
    raise ValueError(f"unknown arrival kind {arrivals['kind']!r}")
