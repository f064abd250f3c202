"""Whole runs of a small cell on the CPU, with the chip check skipped:
the last line's schema, a cell added by files alone, the refusal off a
TPU, and faults planted under the timed path that must read as not
correct."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

from tccsbench import run

from .conftest import CELL, REPO

SECONDS = 1.0


def tiny_run(root, seed=2**31 + 7, traced=False):
    cell = run.load_cell(CELL, root)
    return run.run_cell(cell, seed, SECONDS, traced, time.perf_counter())


def test_last_line_schema(tiny_root, off_chip, capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2**32 + 5),
                   "--seconds", str(SECONDS), "--trace", "0"], root=tiny_root)
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"qps", "p50_ms", "p95_ms", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["checks"] == {"wrong": {"value": 0, "limit": 0},
                              "missing": {"value": 0, "limit": 0}}
    assert err.strip().splitlines()[-2:] == ["check wrong 0 limit 0",
                                             "check missing 0 limit 0"]


def test_cell_from_files_alone(tiny_root, off_chip):
    """The tiny cell, its mix and the ``answered`` metric exist only as
    new files and entries; the harness finds each by name."""
    cell = run.load_cell(CELL, tiny_root)
    assert cell.config["name"] == "tiny" and cell.mix["clients"] == 16
    import jax
    r, g, _ = run.drive(cell, 3, SECONDS, False, jax.devices()[:1],
                        time.perf_counter())
    # the per-layer metrics of BENCHMARK.json list their own cells, so
    # this cell reports only the one added for it
    per_layer = run.report(cell, r, traced=True)
    assert per_layer == {"answered": {"value": float(len(r.records)),
                                      "unit": "queries"}}
    assert run.check(r, g) == {"wrong": 0, "missing": 0}


def test_refuses_without_tpu(capsys):
    rc = run.main(["--workload", "collegemsg.lookup-closed", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=REPO)
    out, err = capsys.readouterr()
    assert rc == 2 and out == ""
    assert "needs a TPU" in err and "'cpu'" in err


def test_one_corrupted_answer_fails(tiny_root, off_chip):
    import jax
    cell = run.load_cell(CELL, tiny_root)
    r, g, _ = run.drive(cell, 4, SECONDS, False, jax.devices()[:1],
                        time.perf_counter())
    assert run.check(r, g) == {"wrong": 0, "missing": 0}
    rec = next(x for x in r.records
               if x.query.mode == "VERTICES" and x.result.vertices)
    v = min(rec.result.vertices)
    import dataclasses
    rec.result = dataclasses.replace(rec.result,
                                     vertices=rec.result.vertices - {v})
    assert run.check(r, g) == {"wrong": 1, "missing": 0}


def _altered(monkeypatch):
    """An answer altered where it is produced: the first member of every
    device answer goes missing."""
    from repro.serving import planner
    real = planner.assemble_device_results

    def drop_one(store, specs, vmask, vermask, prov):
        vmask = np.array(vmask)
        for row in vmask:
            on = np.nonzero(row)[0]
            if on.size:
                row[on[0]] = False
        return real(store, specs, vmask, vermask, prov)
    monkeypatch.setattr(planner, "assemble_device_results", drop_one)


def _half_dropped(monkeypatch):
    """Half of each batch left out: only the first half is executed."""
    from repro.serving.planner import QueryPlanner
    real = QueryPlanner.execute
    monkeypatch.setattr(QueryPlanner, "execute",
                        lambda self, handle, batch:
                        real(self, handle, batch[:max(1, len(batch) // 2)]))


def _stale(monkeypatch):
    """A launch that returns its state unchanged: every device launch
    answers with the first launch's masks."""
    from repro.serving.executor import ShardedExecutor
    real, first = ShardedExecutor.run, {}

    def run_once(self, dix, u, ts, te, bucket):
        if "mask" not in first:
            first["mask"] = real(self, dix, u, ts, te, bucket)
        return first["mask"][:len(u)]
    monkeypatch.setattr(ShardedExecutor, "run", run_once)


@pytest.mark.parametrize("fault", [_altered, _half_dropped, _stale])
def test_fault_reads_not_correct(tiny_root, off_chip, monkeypatch, fault):
    fault(monkeypatch)
    result = tiny_run(tiny_root)
    assert result["correct"] is False
    assert result["checks"]["wrong"]["value"] + \
        result["checks"]["missing"]["value"] > 0
