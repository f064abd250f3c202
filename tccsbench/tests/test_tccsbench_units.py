"""The benchmark's parts on their own: the graph and traffic generators
repeat by seed, the reference answers as the definition says and the
control does not, and the order statistics."""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from itertools import islice

import numpy as np
import pytest

from tccsbench import control, graphgen, loadgen, reference, stats
from tccsbench.run import load_cell

from .conftest import CELL, TINY_CONFIG, TINY_MIX

BIG_SEED = 2**31 + 12345


def tiny_graph():
    return graphgen.make_graph(TINY_CONFIG)


def test_graph_is_the_configurations():
    a, b = tiny_graph(), tiny_graph()
    for x, y in ((a.src, b.src), (a.dst, b.dst), (a.t, b.t)):
        assert np.array_equal(x, y)
    other = graphgen.make_graph(
        dict(TINY_CONFIG, assumed=dict(TINY_CONFIG["assumed"], graph_seed=1)))
    assert not np.array_equal(a.src, other.src)
    assert a.m == 1500 and a.t_max <= 30 and (a.src != a.dst).all()
    # sorted by (t, src, dst), days dense from 1
    order = np.lexsort((a.dst, a.src, a.t))
    assert np.array_equal(order, np.arange(a.m))
    assert set(np.unique(a.t)) == set(range(1, a.t_max + 1))


@pytest.mark.parametrize("name", ["collegemsg", "fb-forum"])
def test_config_structure_is_the_generated_graphs(name):
    """The structure a configuration states for its graph is the graph's."""
    import json

    from .conftest import REPO
    config = json.loads((REPO / "tccsbench" / "configs"
                         / f"{name}.json").read_text())
    g = graphgen.make_graph(config)
    pairs = np.unique(np.minimum(g.src, g.dst).astype(np.int64) * g.n
                      + np.maximum(g.src, g.dst))
    a, b = pairs // g.n, pairs % g.n
    k_max = 0
    while a.size:
        deg = np.bincount(a, minlength=g.n) + np.bincount(b, minlength=g.n)
        keep = (deg[a] > k_max) & (deg[b] > k_max)
        if keep.all():
            k_max += 1
        a, b = a[keep], b[keep]
    deg = (np.bincount(pairs // g.n, minlength=g.n)
           + np.bincount(pairs % g.n, minlength=g.n))
    assert config["structure"]["generated"] == {
        "static_edges": pairs.size, "max_static_degree": int(deg.max()),
        "k_max": k_max, "strata": k_max - 1}


def test_traffic_repeats_by_seed():
    g = tiny_graph()
    one = list(islice(loadgen.queries(g, TINY_MIX, BIG_SEED), 500))
    two = list(islice(loadgen.queries(g, TINY_MIX, BIG_SEED), 500))
    other = list(islice(loadgen.queries(g, TINY_MIX, BIG_SEED + 1), 500))
    warm = list(islice(loadgen.queries(g, TINY_MIX, BIG_SEED, 2), 500))
    assert one == two and one != other and one != warm
    # every seed sends the same mix of k and modes, in the same order
    assert [(q.k, q.mode) for q in one] == [(q.k, q.mode) for q in other]
    assert [q.mode for q in one[:16]].count("COUNT") == 4
    for q in one:
        assert 1 <= q.ts <= q.te <= g.t_max and 0 <= q.u < g.n
        assert q.te - q.ts <= 10


def test_closed_loop_keeps_clients_outstanding():
    """A fake server answers each call's queries together after 20 ms."""
    calls = []

    def submit(qs):
        futs = [Future() for _ in qs]
        calls.append(len(qs))

        def answer():
            time.sleep(0.02)
            for f, q in zip(futs, qs):
                f.set_result(q)
        threading.Thread(target=answer).start()
        return futs

    stream = loadgen.queries(tiny_graph(), TINY_MIX, 1)
    records, t0 = loadgen.closed_loop(submit, stream, 8, 0.3)
    loadgen.settle(records)
    # answers that land while the loop is waking may go out in two calls
    assert calls[0] == 8 and sum(calls) == len(records)
    assert 80 <= len(records) <= 128
    assert all(r.result == r.query for r in records)
    assert all(r.t_send >= t0 and r.t_done >= r.t_send + 0.02
               for r in records)
    # nothing is sent once the window has closed
    assert max(r.t_send for r in records) < t0 + 0.3


def test_closed_loop_sends_each_successor_on_its_answer():
    """A fake server answers the j-th query of a call 30 ms x (j + 1)
    after it: each answer sends its client's next query at once, without
    waiting for the slower answers of its call."""
    calls = []

    def submit(qs):
        futs = [Future() for _ in qs]
        calls.append(len(qs))

        def answer():
            for f, q in zip(futs, qs):
                time.sleep(0.03)
                f.set_result(q)
        threading.Thread(target=answer).start()
        return futs

    stream = loadgen.queries(tiny_graph(), TINY_MIX, 1)
    records, _ = loadgen.closed_loop(submit, stream, 4, 0.25)
    loadgen.settle(records)
    assert calls[:2] == [4, 1]
    # the first answer's successor left before the second answer came
    assert records[4].t_send < records[1].t_done
    assert all(r.result == r.query for r in records)


def test_qps_counts_only_answers():
    """A query that failed ends the time but is no answer."""
    from types import SimpleNamespace

    from tccsbench.run import reader
    from .conftest import REPO
    sent = [loadgen.Sent(None, 0.0, t_done=1.0),
            loadgen.Sent(None, 0.0, t_done=2.0, error=RuntimeError()),
            loadgen.Sent(None, 0.0)]
    qps = reader(REPO, "qps")(SimpleNamespace(records=sent, t_first=0.0))
    assert qps == pytest.approx(0.5)


def test_closed_loop_gives_up_on_missing_answers():
    def submit(qs):
        return [Future() for _ in qs]
    stream = loadgen.queries(tiny_graph(), TINY_MIX, 1)
    records, _ = loadgen.closed_loop(submit, stream, 4, 0.05, grace_s=0.1)
    assert len(records) == 4 and all(r.t_done is None for r in records)


def test_reference_matches_definition():
    """Against the repository's brute-force oracle, on every mode's
    answers of a query sample."""
    from repro.core.kcore import tccs_oracle
    from repro.core.temporal_graph import TemporalGraph
    g = tiny_graph()
    tg = TemporalGraph(g.n, g.src, g.dst, g.t)
    mix = dict(TINY_MIX, window={"kind": "around_edge",
                                 "before_days": [0, 15],
                                 "after_days": [0, 15]})
    nonempty = 0
    for q in islice(loadgen.queries(g, mix, 5), 120):
        want = frozenset(tccs_oracle(tg, q.k, q.u, q.ts, q.te))
        assert reference.component(g, q.u, q.ts, q.te, q.k) == want
        nonempty += bool(want)
    assert nonempty > 30


def test_control_fails_the_comparison(tiny_root, off_chip):
    """Within one set-up, the program's window reads correct and the
    control's, put in the program's place, reads not correct on every
    seed tried, through the comparison and limits a run uses."""
    cell = load_cell(CELL, tiny_root)
    out = control.windows(cell, [1], [1, 2, 2**33 + 3], 1.0,
                          time.perf_counter())
    assert out["program"][1]["correct"] is True
    for line in out["control"].values():
        assert line["correct"] is False and line["checks"]["wrong"] > 0


def test_percentile():
    assert stats.percentile([3, 1, 2], 50) == 2
    assert stats.percentile([1, 2, 3, 4], 95) == pytest.approx(3.85)
    assert stats.percentile([5], 95) == 5
