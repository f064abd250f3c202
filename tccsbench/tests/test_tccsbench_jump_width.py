"""The reader of ``jump_width``, the node window each device launch
propagates over: what it gives from a run's counters, nothing from a
program that counts no width, its entry, and on a whole run of the small
cell the widest stratum of the fused mirror."""

from __future__ import annotations

import time

import numpy as np
import pytest

from tccsbench import run

from .conftest import CELL, REPO
from .test_tccsbench_layers import synthetic


@pytest.mark.parametrize("counters, want", [
    ({"jump_rounds": 36, "jump_launches": 3, "jump_width": 3 * 36797},
     36797.0),
    # launches counted, width not: a program without the stratum window
    ({"jump_rounds": 36, "jump_launches": 3}, None),
    ({}, None),
])
def test_jump_width_reader(counters, want):
    assert run.reader(REPO, "jump_width")(synthetic(counters)) == want


def test_jump_width_entry():
    cell = run.load_cell("fb-forum.lookup-closed", REPO)
    m = cell.metrics["jump_width"]
    assert (m["kind"], m["better"], m["layer"], m["moves"]) == (
        "per_layer", "lower", "device programs", "qps")
    assert m["workloads"] == ["collegemsg.lookup-closed",
                              "fb-forum.lookup-closed"]


def test_jump_width_on_a_whole_run(tiny_root, off_chip):
    import jax

    from repro.core.pecb_index import build_stratified_index

    cell = run.load_cell(CELL, tiny_root)
    r, g, _ = run.drive(cell, 2**31 + 98, 1.0, False, jax.devices()[:1],
                        time.perf_counter())
    sx = build_stratified_index(g)
    widest = int(np.diff(sx.knode_ptr).max())
    assert run.reader(REPO, "jump_width")(r) == widest < sx.num_nodes
    assert r.counters["jump_width"] == widest * r.counters["jump_launches"]
