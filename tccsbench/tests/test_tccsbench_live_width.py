"""The reader of ``live_width``, the live-list width each device launch
propagates over: what it gives from a run's counters, nothing from a
program that counts no live width, its entry, and on a whole run of the
small cell the longest live list of the fused mirror."""

from __future__ import annotations

import time

import pytest

from tccsbench import run

from .conftest import CELL, REPO
from .test_tccsbench_layers import synthetic


@pytest.mark.parametrize("counters, want", [
    ({"jump_rounds": 36, "jump_launches": 3, "jump_width": 3 * 36797,
      "live_width": 3 * 1878}, 1878.0),
    # launches counted, live width not: a program without live lists
    ({"jump_rounds": 36, "jump_launches": 3, "jump_width": 3 * 36797},
     None),
    ({}, None),
])
def test_live_width_reader(counters, want):
    assert run.reader(REPO, "live_width")(synthetic(counters)) == want


def test_live_width_entry():
    cell = run.load_cell("collegemsg.lookup-closed", REPO)
    m = cell.metrics["live_width"]
    assert (m["kind"], m["better"], m["layer"], m["moves"]) == (
        "per_layer", "lower", "device programs", "qps")
    assert m["workloads"] == ["collegemsg.lookup-closed",
                              "fb-forum.lookup-closed"]


def test_live_width_on_a_whole_run(tiny_root, off_chip):
    import jax

    from repro.core.batch_query import _host_layout
    from repro.core.pecb_index import build_stratified_index

    cell = run.load_cell(CELL, tiny_root)
    r, g, _ = run.drive(cell, 2**31 + 99, 1.0, False, jax.devices()[:1],
                        time.perf_counter())
    sx = build_stratified_index(g)
    meta, _ = _host_layout(sx)
    longest = meta["max_live_nodes"]
    assert run.reader(REPO, "live_width")(r) == longest < g.n
    assert r.counters["live_width"] == longest * r.counters["jump_launches"]
