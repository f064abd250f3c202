"""Seeded temporal graphs at a configuration's published counts.

A copy of the generator the serving code's own tests and demos use
(``gen_temporal_graph``: Zipf vertex popularity, uniform days with a share
of "bursty" edges that repeat the previous edge's day, timestamps densified
to 1..#days), kept here so that the benchmark makes its data without
calling the system under test.

The graph comes from the configuration's ``graph_seed``, not from the
run's ``--seed``, which draws the queries: the deployment serves one
graph, and the work of a launch depends on it. Relabelling the vertices
by the run's seed was tried and dropped: the index differed a little by
seed (the forest breaks ties by edge id), and on one v5e a launch of 32
took 3,439, 3,572 or 3,658 ms by seed, the same for every launch of a
run, while two runs of one seed agreed to 0.01%.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Graph:
    """Undirected temporal multigraph, edges sorted by (t, src, dst)."""

    n: int
    src: np.ndarray     # int32[m]
    dst: np.ndarray     # int32[m]
    t: np.ndarray       # int32[m], days 1..t_max

    @property
    def m(self) -> int:
        return int(self.src.shape[0])

    @property
    def t_max(self) -> int:
        return int(self.t[-1]) if self.m else 0


def _sorted(n: int, src, dst, t) -> Graph:
    order = np.lexsort((dst, src, t))
    return Graph(n, src[order].astype(np.int32), dst[order].astype(np.int32),
                 t[order].astype(np.int32))


def structure(n: int, m: int, t_max: int, *, seed: int, power: float,
              burstiness: float) -> Graph:
    """The generator: ``n`` vertices, ``m`` temporal edges over at most
    ``t_max`` days, no self-loops."""
    rng = np.random.default_rng(seed)
    pop = np.arange(1, n + 1, dtype=np.float64) ** (-power)
    pop /= pop.sum()
    u = rng.choice(n, size=2 * m, p=pop).astype(np.int64)
    src, dst = u[:m], u[m:]
    fix = src == dst
    dst[fix] = (src[fix] + 1 + rng.integers(0, n - 1, fix.sum())) % n
    t = rng.integers(1, t_max + 1, size=m)
    nb = int(burstiness * m)
    if nb and m > 1:
        idx = rng.integers(1, m, size=nb)
        t[idx] = t[idx - 1]
    g = _sorted(n, src, dst, t)
    # densify the days actually used to 1..#distinct
    _, inv = np.unique(g.t, return_inverse=True)
    return Graph(n, g.src, g.dst, (inv + 1).astype(np.int32))


def make_graph(config: dict) -> Graph:
    """The graph a deployment of ``config`` serves."""
    # a count the source does not publish (a day span) sits in ``assumed``
    size = {**config["assumed"], **config["published"]}
    return structure(size["vertices"], size["temporal_edges"], size["days"],
                     seed=size["graph_seed"], power=size["zipf_power"],
                     burstiness=size["burst_share"])
