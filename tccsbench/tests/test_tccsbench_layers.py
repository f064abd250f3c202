"""The readers of the engine's own layer numbers: each gives what it
should from a run's counters, histograms and build stages, and nothing
when the program does not record its input; and on a whole run of the
small cell each finds what the program recorded."""

from __future__ import annotations

import time

import pytest

from repro.obs.registry import LatencyHistogram

from tccsbench import run

from .conftest import CELL, REPO

LAYERS = ("jump_rounds", "assemble_ms_per_q", "launch_gap_ms",
          "build_sweep_s", "build_sweep_load_s", "build_sweep_host_s",
          "build_upload_s")


def hist(*seconds):
    h = LatencyHistogram()
    for s in seconds:
        h.add(s)
    return h


def synthetic(counters=None, hists=None, stages=None):
    return run.Run(records=[], t_first=0.0, t_gave_up=0.0, setup_s=1.0,
                   counters=counters or {}, hists=hists or {},
                   stages=stages or {}, trace=None)


FULL = synthetic(
    counters={"jump_rounds": 36, "jump_launches": 3, "device_queries": 96},
    hists={"device_assemble": hist(0.002, 0.004, 0.006),
           "launch_gap": hist(0.010, 0.014)},
    stages={"core_times": 10.0, "core_times.prepare": 1.5,
            "core_times.dispatch": 2.0, "core_times.sweep": 6.0,
            "core_times.compress": 0.25, "forest": 1.0, "device": 0.5})

WANT = {"jump_rounds": 12.0, "assemble_ms_per_q": 12.0 / 96,
        "launch_gap_ms": 12.0, "build_sweep_s": 6.0,
        "build_sweep_load_s": 2.0, "build_sweep_host_s": 1.75,
        "build_upload_s": 0.5}


@pytest.mark.parametrize("name", LAYERS)
def test_layer_reader(name):
    read = run.reader(REPO, name)
    assert read(FULL) == pytest.approx(WANT[name])
    # a program that records none of it (the parent of these counters)
    assert read(synthetic()) is None


@pytest.mark.parametrize("name", LAYERS)
def test_layer_entry(name):
    """Each reader has its entry, listed for the two chip cells."""
    cell = run.load_cell("collegemsg.lookup-closed", REPO)
    m = cell.metrics[name]
    assert m["kind"] == "per_layer" and m["better"] == "lower"
    assert m["workloads"] == ["collegemsg.lookup-closed",
                              "fb-forum.lookup-closed"]


def test_readers_on_a_whole_run(tiny_root, off_chip):
    import jax

    cell = run.load_cell(CELL, tiny_root)
    r, _, _ = run.drive(cell, 2**31 + 99, 1.0, False, jax.devices()[:1],
                        time.perf_counter())
    got = {name: run.reader(REPO, name)(r) for name in LAYERS}
    assert all(v is not None for v in got.values()), got
    assert got["jump_rounds"] >= 1
    assert got["assemble_ms_per_q"] > 0 and got["launch_gap_ms"] > 0
    # the CPU builds with the host engine: nothing is handed to a device
    assert got["build_sweep_load_s"] == 0.0
    parts = (got["build_sweep_s"] + got["build_sweep_load_s"]
             + got["build_sweep_host_s"])
    assert 0 < parts <= r.stages["core_times"]
    assert r.counters["jump_launches"] == r.counters["device_batches"]
