"""The one traffic generator. A mix is a data file under ``traffic/``
that names its parameters; this module reads them.

Query stream (``queries``), drawn from the run's seed, the same for every
loop: query ``i`` asks about ``ks[i % len(ks)]`` in the mode at position
``(i // len(ks)) % len(cycle)`` of the mode cycle (``"modes": {"VERTICES":
3, "COUNT": 1}`` is the cycle V V V C), so every seed sends the same mix of
k and modes. The vertex and window come from the mix's ``window`` kind,
``around_edge``: draw a temporal edge uniformly, ask about its sender (so
vertices are weighted by activity), over the edge's day minus
``before_days`` to plus ``after_days`` (each uniform, both ends included),
clamped to the graph's days.

The loop (``"loop": "closed"``, the only one so far) keeps ``clients``
queries outstanding. A query is sent as soon as the answer of the one
before it in its client arrives, whatever the others are doing; answers
that are in when the loop wakes (the answers of one launch, or cache hits
at submit) send their successors in one call. Sending stops at the end of the window; what is outstanding
then drains.
"""

from __future__ import annotations

import dataclasses
import time
from concurrent.futures import FIRST_COMPLETED, wait
from contextlib import nullcontext
from typing import Callable, Iterator

import numpy as np

CHUNK = 4096


@dataclasses.dataclass(frozen=True)
class Query:
    u: int
    ts: int
    te: int
    k: int
    mode: str


@dataclasses.dataclass
class Sent:
    """One query of the window: when it went out, when and what came back."""

    query: Query
    t_send: float
    future: object = None
    t_done: float | None = None
    result: object = None
    error: BaseException | None = None


def _mode_cycle(modes: dict) -> list[str]:
    return [m for m, w in modes.items() for _ in range(int(w))]


def queries(g, mix: dict, seed: int, stream: int = 1) -> Iterator[Query]:
    """The endless query stream of ``mix`` on ``g`` under ``seed``; other
    ``stream`` numbers give independent streams (the warm-up's is 2)."""
    rng = np.random.default_rng([int(seed), int(stream)])
    ks, cycle, win = list(mix["ks"]), _mode_cycle(mix["modes"]), mix["window"]
    if win["kind"] != "around_edge" or mix["loop"] != "closed":
        raise ValueError(f"unknown window kind {win['kind']!r} or loop "
                         f"{mix['loop']!r}")
    i = 0
    while True:
        e = rng.integers(0, g.m, CHUNK)
        u = g.src[e]
        lo_b, hi_b = win["before_days"]
        lo_a, hi_a = win["after_days"]
        ts = np.maximum(1, g.t[e] - rng.integers(lo_b, hi_b + 1, CHUNK))
        te = np.minimum(g.t_max, g.t[e] + rng.integers(lo_a, hi_a + 1, CHUNK))
        for j in range(CHUNK):
            yield Query(int(u[j]), int(ts[j]), int(te[j]), int(ks[i % len(ks)]),
                        cycle[(i // len(ks)) % len(cycle)])
            i += 1


def _stamp(rec: Sent, clock: Callable[[], float]):
    def done(fut) -> None:
        rec.t_done = clock()
        rec.error = fut.exception()
        if rec.error is None:
            rec.result = fut.result()
    return done


def closed_loop(submit: Callable, stream: Iterator[Query], clients: int,
                seconds: float, *, grace_s: float = 60.0,
                annotate: Callable = lambda name: nullcontext(),
                clock: Callable[[], float] = time.perf_counter
                ) -> tuple[list[Sent], float]:
    """Drive ``submit(list[Query]) -> list[Future]`` for ``seconds``.

    Returns every query sent and the time of the first send. A query whose
    answer has not come ``grace_s`` after the window closed is left
    unanswered (``t_done`` None)."""
    records: list[Sent] = []

    def send(n: int) -> set:
        qs = [next(stream) for _ in range(n)]
        t = clock()
        with annotate("tccsbench.submit"):
            futs = submit(qs)
        for q, f in zip(qs, futs):
            rec = Sent(q, t, f)
            records.append(rec)
            f.add_done_callback(_stamp(rec, clock))
        return set(futs)

    t0 = clock()
    t_close, pending, n_next = t0 + seconds, set(), clients
    while True:
        if n_next and clock() < t_close:
            pending |= send(n_next)
        n_next = 0
        ready = {f for f in pending if f.done()}
        if not ready and pending:
            with annotate("tccsbench.wait"):
                wait(pending, return_when=FIRST_COMPLETED,
                     timeout=max(0.0, t_close + grace_s - clock()))
            ready = {f for f in pending if f.done()}
        if not ready:
            break
        pending -= ready
        n_next = len(ready)
    return records, t0


def settle(records: list[Sent], timeout_s: float = 5.0) -> None:
    """Wait until every resolved future's stamp has run: a waiter can wake
    between a future's resolution and its done-callbacks."""
    end = time.perf_counter() + timeout_s
    while (any(r.t_done is None and r.future.done() for r in records)
           and time.perf_counter() < end):
        time.sleep(0.001)
