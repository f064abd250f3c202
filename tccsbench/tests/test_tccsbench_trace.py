"""The trace reduction, on a hand-made trace and on a small trace recorded
on a TPU v5e."""

from __future__ import annotations

import json
from pathlib import Path

import pytest

from tccsbench import trace

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_trace.json"


def hand_trace():
    """Window 0..100 ns. Ops busy 10..40 (two overlapping) and 60..70;
    idle 0..10 (submit), 40..60 (wait), 70..100 (no annotation)."""
    return {"planes": [
        {"name": "/device:TPU:0", "device": True, "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_batch_query(123)", 10, 30], ["jit_other", 60, 10]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 10, 20], ["gather.2", 20, 20],
                ["copy.3", 60, 10]]}]},
        {"name": "/host:CPU", "device": False, "lines": [
            {"name": "python", "events": [
                ["tccsbench.window", 0, 100], ["tccsbench.submit", 0, 12],
                ["tccsbench.wait", 38, 30]]}]}]}


def test_hand_trace():
    s = trace.summarize(hand_trace())
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(40e-9)
    assert s["program_s"] == {"batch_query": pytest.approx(30e-9),
                              "other": pytest.approx(10e-9)}
    # gather.2 runs inside fusion.1's last 10 ns: self times 10 and 20
    assert s["device_ops"] == [["gather.2", pytest.approx(20e-9)],
                               ["fusion.1", pytest.approx(10e-9)],
                               ["copy.3", pytest.approx(10e-9)]]
    assert [g[0] for g in s["idle_gaps"]] == [
        "unannotated", "tccsbench.wait", "tccsbench.submit"]
    assert [g[1] for g in s["idle_gaps"]] == pytest.approx(
        [30e-9, 20e-9, 10e-9])


def test_clipped_to_window():
    tr = hand_trace()
    tr["planes"][1]["lines"][0]["events"][0] = ["tccsbench.window", 15, 50]
    s = trace.summarize(tr)
    assert s["window_s"] == pytest.approx(50e-9)
    assert s["busy_s"] == pytest.approx(25e-9 + 5e-9)


def test_no_device_work_is_an_error():
    tr = hand_trace()
    tr["planes"][0]["lines"][1]["events"] = []
    with pytest.raises(ValueError, match="no device operation"):
        trace.summarize(tr)


def test_unknown_device_kind_is_an_error():
    assert trace.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        trace.peaks("cpu")


def test_recorded_v5e_trace():
    """A trace of a small cell's window on one v5e: busy time is the union
    of the op intervals, at most the window, and the query program's
    device time is read from the module line."""
    tr = json.loads(FIXTURE.read_text())
    s = trace.summarize(tr)
    expect = json.loads((FIXTURE.with_suffix(".expect.json")).read_text())
    for key in ("window_s", "busy_s"):
        assert s[key] == pytest.approx(expect[key], rel=1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["program_s"]["batch_query"] == pytest.approx(
        expect["batch_query_s"], rel=1e-9)
    assert len(s["device_ops"]) == 10 and len(s["idle_gaps"]) == 10
    assert {g[0] for g in s["idle_gaps"]} <= {
        "tccsbench.submit", "tccsbench.wait", "unannotated"}
