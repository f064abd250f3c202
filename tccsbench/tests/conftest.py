"""A small cell of the benchmark's own, added the way a later change adds
one: new files under ``configs/``, ``traffic/`` and ``metrics/`` and new
entries in a copy of ``BENCHMARK.json``, with no existing file edited."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny.lookup-closed"

TINY_CONFIG = {
    "name": "tiny",
    "source": "a small graph for the benchmark's CPU tests",
    "published": {"vertices": 120, "temporal_edges": 1500, "days": 30},
    "assumed": {"zipf_power": 1.2, "burst_share": 0.35, "graph_seed": 0},
    "reduced": [],
    "engine": {"max_batch": 256, "flush_ms": 2.0, "min_bucket": 8,
               "host_threshold": 8, "cache_capacity": 4096},
}
TINY_MIX = {
    "loop": "closed", "clients": 16,
    "modes": {"VERTICES": 3, "COUNT": 1}, "ks": [2, 3, 4, 5],
    "window": {"kind": "around_edge", "before_days": [0, 5],
               "after_days": [0, 5]},
}
ANSWERED = '''"""Queries answered in the window (a metric added by a file)."""


def read(run):
    return sum(r.t_done is not None for r in run.records)
'''


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench")
    shutil.copytree(REPO / "tccsbench", root / "tccsbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    pkg = root / "tccsbench"
    (pkg / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (pkg / "traffic" / "tiny-closed.json").write_text(json.dumps(TINY_MIX))
    (pkg / "metrics" / "answered.py").write_text(ANSWERED)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "tccsbench/configs/tiny.json",
                             "reduced": [], "why": "CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "tiny",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "CPU tests"})
    bench["per_layer"].append({"name": "answered", "unit": "queries",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "batcher", "moves": "qps",
                               "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def off_chip(monkeypatch):
    """Skip the harness's look for a chip, its peak table and its compile
    cache, so that the rest of a run drives the engine on the CPU."""
    import jax

    from tccsbench import run, trace
    monkeypatch.setattr(run, "check_platform",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(run, "enable_cache", lambda: "off")
    monkeypatch.setattr(trace, "peaks", lambda kind: {})
